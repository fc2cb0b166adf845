"""Device calls per decode round: the decode programs (``jit_decode``) each
round of the traced window ran on the chip, mean over the rounds
(bench/rounds.py). One call per sequence makes it the sequences per
round; a batched decode makes it 1."""
import statistics

from bench.rounds import decode_rounds


def read(run):
    rounds = decode_rounds(run)
    return statistics.fmean(len(d) for _, d in rounds) if rounds else None
