"""Operations and bytes the model's work needs, from shapes alone.

Counted from the traffic (which tokens were decoded at which context, which
prompt chunks were prefilled), never from what an implementation happens to
do, so the count is the same whatever computes it.  ``m`` is a configuration
file's ``model`` group; ``kv_bits`` None means the pool holds float32.

A matmul of an (n, k) by (k, m) operand is 2 n k m operations.  Attention
for one query over L keys is 4 * heads * head_dim * L operations (scores and
the weighted sum) and reads L keys and values of every KV head.
"""
from __future__ import annotations


def _layer_matmul_params(m) -> int:
    d, h, kvh, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                         m["head_dim"], m["d_ff"])
    return d * (h + 2 * kvh) * hd + h * hd * d + 3 * d * ff


def attention_flops(m, keys: int) -> float:
    """One query position over `keys` keys, all layers."""
    return 4.0 * m["num_layers"] * m["num_heads"] * m["head_dim"] * keys


def decode_token_flops(m, context: int) -> float:
    """Model operations of one decoded token whose step attends `context`
    positions (the prompt, the tokens so far and itself): every projection,
    attention and the vocabulary head."""
    return (2.0 * (m["num_layers"] * _layer_matmul_params(m)
                   + m["d_model"] * m["vocab_size"])
            + attention_flops(m, context))


def prefill_token_flops(m, position: int) -> float:
    """Model operations of the prompt token at `position` (0-based): every
    projection and causal attention over positions 0..position; no head."""
    return (2.0 * m["num_layers"] * _layer_matmul_params(m)
            + attention_flops(m, position + 1))


def kv_bytes(m, keys: int, kv_bits, page: int) -> float:
    """Bytes of keys and values for `keys` positions, all layers, as the
    pool stores them (int4/int8 payloads plus one exponent byte per page
    and KV head, or float32)."""
    per = m["num_layers"] * 2 * m["num_kv_heads"]
    if kv_bits is None:
        return per * keys * m["head_dim"] * 4.0
    pages = -(-keys // page)
    return per * (keys * m["head_dim"] * kv_bits / 8.0 + pages)


def paged_decode(m, context: int, kv_bits, page: int):
    """(operations, bytes) of decode attention for one token at `context`
    keys: scores and weighted sum, the live keys and values, the query in
    and the output out (float32)."""
    qo = 2.0 * m["num_layers"] * m["num_heads"] * m["head_dim"] * 4.0
    return (attention_flops(m, context),
            kv_bytes(m, context, kv_bits, page) + qo)


def mq_prefill(m, start: int, tokens: int, kv_bits, page: int):
    """(operations, bytes) of chunk attention for `tokens` prompt positions
    from `start`: each attends causally to every position up to itself; the
    keys and values up to the chunk's end are read once, the queries in and
    the outputs out (float32)."""
    ops = sum(attention_flops(m, p + 1) for p in range(start, start + tokens))
    qo = 2.0 * m["num_layers"] * tokens * m["num_heads"] * m["head_dim"] * 4.0
    return ops, kv_bytes(m, start + tokens, kv_bits, page) + qo


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Least time the chip could take (the larger of operations over the
    bf16 peak and bytes over HBM bandwidth) as a share of `seconds`."""
    least = max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return least / seconds
