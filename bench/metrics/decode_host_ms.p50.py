"""A decode round's time on the host clock less the device time of the
decode programs it ran (bench/rounds.py): the part of the round the
chip spent waiting for the host (dispatch, each call's launch and
return, the loop between calls). Median over the traced window's
rounds."""
from bench.harness import percentile
from bench.rounds import decode_rounds


def read(run):
    return percentile([c[2] - c[1] - sum(d) for c, d in decode_rounds(run)],
                      50)
