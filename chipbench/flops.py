"""Model operations and least bytes of each step, from shapes alone.

Operations count a multiply-add as two.  Recomputation (rematerialisation)
is not counted: these are the operations the model needs.  Causal attention
counts each query against the keys at or before it, half of S^2 and the
diagonal.  Configurations are read by their published (HF) key names.
"""

from __future__ import annotations


def layer_matmul_params(c: dict) -> int:
    d, h, kv, hd, f = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"], c["intermediate_size"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def unembed_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def causal_pairs(seq: int) -> int:
    """(query, key) pairs of one causal sequence: S(S+1)/2."""
    return seq * (seq + 1) // 2


def attention_flops(c: dict, pairs: int) -> int:
    """QK^T and PV, forward, over ``pairs`` (query, key) pairs."""
    return 4 * c["num_attention_heads"] * c["head_dim"] * pairs * c["num_hidden_layers"]


def forward_flops(c: dict, batch: int, seq: int) -> int:
    tokens = batch * seq
    return (2 * (c["num_hidden_layers"] * layer_matmul_params(c) + unembed_params(c)) * tokens
            + attention_flops(c, batch * causal_pairs(seq)))


def train_step_flops(c: dict, batch: int, seq: int) -> int:
    """Forward and backward: three times the forward."""
    return 3 * forward_flops(c, batch, seq)


def wave_flops(c: dict, batch: int, prompt: int, output: int) -> int:
    """One serving wave: every position of prompt and output through the
    layers, and the unembedding where a token is produced (the last prompt
    position and each output position but the last, ``output`` in all)."""
    positions = prompt + output
    return batch * (2 * c["num_hidden_layers"] * layer_matmul_params(c) * positions
                    + 2 * unembed_params(c) * output) \
        + attention_flops(c, batch * causal_pairs(positions))


def weight_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """The weights a decode step must read: every layer's matrices and norms,
    the final norm and the unembedding (one embedding row per token is left
    out)."""
    d = c["hidden_size"]
    return dtype_bytes * (c["num_hidden_layers"] * (layer_matmul_params(c) + 2 * d)
                          + d + unembed_params(c))


def cache_row_bytes(c: dict, batch: int, dtype_bytes: int = 2) -> int:
    """Keys and values of one position, all layers, the whole batch."""
    return (2 * c["num_hidden_layers"] * batch * c["num_key_value_heads"]
            * c["head_dim"] * dtype_bytes)


def decode_step_bytes(c: dict, batch: int, position: int) -> int:
    """Least bytes of the decode step that writes ``position``: the weights,
    the ``position + 1`` valid cache rows read, and the one row written."""
    return weight_bytes(c) + (position + 2) * cache_row_bytes(c, batch)


def wave_bytes(c: dict, batch: int, prompt: int, output: int) -> int:
    """Least bytes of all decode steps of one wave (positions 0 .. P+O-1)."""
    n = prompt + output
    return n * weight_bytes(c) + (causal_pairs(n) + n) * cache_row_bytes(c, batch)
