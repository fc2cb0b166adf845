"""Work counts: the operations and bytes that a step requires, from the
configuration's published keys, whatever implementation runs it.

- Operations: 2 per multiply-add of the layers' weight matrices for each
  token; attention over the key positions actually in use (the causal
  half in prefill: ``P (P + 1) / 2`` query-key pairs, 4 ``head_dim``
  operations per pair and head for scores and values); and the
  unembedding only at the positions whose logits the step produces (the
  last one). Embedding lookups, norms and biases are not counted.
- Bytes: every weight the step needs read once per call (layers, biases,
  norms and the unembedding matrix, but only the embedding rows looked
  up), the key and value positions in use, and the new keys and values
  written. A decode call is one round of the executor, whatever number
  of device programs the program runs for it: batching the round's
  sequences into one program is a gain this count can show.

So an implementation that scores masked cache positions, or computes
causal blocks it could skip, reads below 100% of its roofline, never
above.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


class Decoder:
    """Counts of a decoder of grouped-query attention layers."""

    def __init__(self, cfg: dict, glu: bool, biases: tuple,
                 norm_params: int = 1):
        d, H = cfg["hidden_size"], cfg["num_attention_heads"]
        KV, ff = cfg["num_key_value_heads"], cfg["intermediate_size"]
        L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
        hd = d // H
        b = DTYPE_BYTES[cfg["serve_dtype"]]
        self.d, self.V = d, V
        # weight-matrix parameters of all layers
        self.matmul_params = L * (2 * d * H * hd + 2 * d * KV * hd
                                  + (3 if glu else 2) * d * ff)
        width = {"q": H * hd, "k": KV * hd, "v": KV * hd, "o": d,
                 "up": ff, "gate": ff, "down": d}
        small = L * (sum(width[k] for k in biases) + 2 * norm_params * d) \
            + norm_params * d
        self.weight_bytes = b * (self.matmul_params + small + V * d)
        self.kv_bytes_per_token = b * L * 2 * KV * hd
        self.flops_per_pair = 4 * L * H * hd
        self.embed_row_bytes = b * d

    def prefill(self, P: int) -> tuple:
        """(operations, bytes) of one prefill of ``P`` tokens."""
        flops = 2 * self.matmul_params * P \
            + self.flops_per_pair * P * (P + 1) // 2 + 2 * self.d * self.V
        nbytes = self.weight_bytes + P * (self.kv_bytes_per_token
                                          + self.embed_row_bytes)
        return flops, nbytes

    def decode(self, lens) -> tuple:
        """(operations, bytes) of one decode call that advances one
        sequence per entry of ``lens``, each attending over that many
        positions, the new one included: the weights are read once for
        the call, and each sequence's cache and embedding row."""
        flops = sum(2 * self.matmul_params + self.flops_per_pair * n
                    + 2 * self.d * self.V for n in lens)
        nbytes = self.weight_bytes + sum(n * self.kv_bytes_per_token
                                         + self.embed_row_bytes for n in lens)
        return flops, nbytes
