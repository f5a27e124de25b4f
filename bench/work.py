"""Operations and bytes the model's work needs, from shapes alone.

Counted from the traffic (which tokens were decoded at which context, which
prompt chunks were prefilled), never from what an implementation happens to
do, so the count is the same whatever computes it.  ``m`` is a configuration
file's ``model`` group; ``kv_bits`` None means the pool holds float32.

The stack is the file's statement (``cell.py``): ``layers``, or
``num_layers`` dense layers.  Each layer here is GQA/MHA attention with a
gated MLP (``mlp: "dense"``, width ``d_ff``), a mixture of experts
(``mlp: "moe"``: the file's ``moe`` group; a token runs ``top_k`` routed
experts and ``num_shared`` shared ones of width ``moe.d_ff``, and the
router) or no MLP (``"none"``).  A layer entry that states ``window`` W
attends to at most W positions: the query's own and the W - 1 before it.

A matmul of an (n, k) by (k, m) operand is 2 n k m operations.  Attention
for one query over L keys is 4 * heads * head_dim * L operations (scores and
the weighted sum) and reads L keys and values of every KV head.
"""
from __future__ import annotations

DENSE = {"kind": "attn", "mlp": "dense"}


def _layers(m) -> list:
    """Every layer of the stack in order, as its stated fields."""
    if "layers" not in m:
        return [DENSE] * m["num_layers"]
    out = [dict(DENSE, **spec) for g in m["layers"]
           for _ in range(g["repeat"]) for spec in g["period"]]
    if len(out) != m["num_layers"]:
        raise ValueError(f"num_layers is {m['num_layers']} but layers "
                         f"states {len(out)}")
    for spec in out:
        if spec["kind"] != "attn" or spec.get("cross_attn") or m.get("mla"):
            raise ValueError(f"no count for the layer {spec} (GQA/MHA "
                             f"self-attention only)")
    return out


def _mlp_params(m, spec) -> int:
    d = m["d_model"]
    if spec["mlp"] == "dense":
        return 3 * d * m["d_ff"]
    if spec["mlp"] == "moe":
        e = m["moe"]
        experts = e["top_k"] + e.get("num_shared", 0)
        return experts * 3 * d * e["d_ff"] + d * e["num_experts"]
    if spec["mlp"] == "none":
        return 0
    raise ValueError(f"no count for the MLP {spec['mlp']!r}")


def _matmul_params(m) -> int:
    """Weights one token multiplies by in the stack (the head apart)."""
    d, h, kvh, hd = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                     m["head_dim"])
    attn = d * (h + 2 * kvh) * hd + h * hd * d
    return sum(attn + _mlp_params(m, spec) for spec in _layers(m))


def _keys(spec, end: int, queries: int = 1) -> int:
    """Key positions that `queries` consecutive queries ending at position
    end - 1 read in one layer: all before them, or their windows' union.
    A window of W is taken as the query's own position and the W - 1 before
    it; the program has no window yet, so the ``model_config`` PR that adds
    ``LayerSpec.window`` has to confirm this against the program's mask and
    the source's ``sliding_window``."""
    w = spec.get("window")
    return end if w is None else min(end, w + queries - 1)


def attention_flops(m, keys: int) -> float:
    """One query position over `keys` keys (fewer in a windowed layer), all
    layers."""
    return 4.0 * m["num_heads"] * m["head_dim"] * sum(
        _keys(spec, keys) for spec in _layers(m))


def decode_token_flops(m, context: int) -> float:
    """Model operations of one decoded token whose step attends `context`
    positions (the prompt, the tokens so far and itself): every projection,
    attention and the vocabulary head."""
    return (2.0 * (_matmul_params(m) + m["d_model"] * m["vocab_size"])
            + attention_flops(m, context))


def prefill_token_flops(m, position: int) -> float:
    """Model operations of the prompt token at `position` (0-based): every
    projection and causal attention over positions 0..position; no head."""
    return 2.0 * _matmul_params(m) + attention_flops(m, position + 1)


def kv_bytes(m, keys: int, kv_bits, page: int, queries: int = 1) -> float:
    """Bytes of keys and values that `queries` consecutive queries up to
    position keys - 1 read, all layers, as the pool stores them (int4/int8
    payloads plus one exponent byte per page and KV head, or float32)."""
    total = 0.0
    for spec in _layers(m):
        n, per = _keys(spec, keys, queries), 2 * m["num_kv_heads"]
        if kv_bits is None:
            total += per * n * m["head_dim"] * 4.0
        else:
            total += per * (n * m["head_dim"] * kv_bits / 8.0 + -(-n // page))
    return total


def _qo_bytes(m, tokens: int) -> float:
    """Queries in and outputs out, float32, all layers."""
    return 2.0 * len(_layers(m)) * tokens * m["num_heads"] * m["head_dim"] * 4.0


def paged_decode(m, context: int, kv_bits, page: int):
    """(operations, bytes) of decode attention for one token at `context`
    keys: scores and weighted sum, the live keys and values, the query in
    and the output out (float32)."""
    return (attention_flops(m, context),
            kv_bytes(m, context, kv_bits, page) + _qo_bytes(m, 1))


def mq_prefill(m, start: int, tokens: int, kv_bits, page: int):
    """(operations, bytes) of chunk attention for `tokens` prompt positions
    from `start`: each attends causally to every position up to itself; the
    keys and values up to the chunk's end are read once, the queries in and
    the outputs out (float32)."""
    ops = sum(attention_flops(m, p + 1) for p in range(start, start + tokens))
    return ops, (kv_bytes(m, start + tokens, kv_bits, page, tokens)
                 + _qo_bytes(m, tokens))


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Least time the chip could take (the larger of operations over the
    bf16 peak and bytes over HBM bandwidth) as a share of `seconds`."""
    least = max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return least / seconds
