"""Plain reference forward for a stack of attention layers with dense or
mixture-of-experts MLPs, in NumPy, one sequence and one token at a time.

For tests at toy widths: `smoke-moe.json` names it by its ``reference``
key.  Independent of the program's model code: it reads the configuration
file's ``model`` group, draws the same seeded weights (weights.py) one
period at a time, and runs

    x = embed[tokens]
    per layer:  h = rms(x) * (1 + g1)
                q, k, v = h Wq, h Wk, h Wv;  q, k = rope(q), rope(k)
                x += softmax(q k^T / sqrt(hd) + causal) v  Wo
                h = rms(x) * (1 + g2)
                dense:  x += (silu(h Wg) * h Wu) Wd
                moe:    per token, p = softmax(h Wr) (sigmoid for
                        ``gate: "sigmoid"``); for each of the top_k experts
                        e, at weight p_e / sum of the top_k p,
                        x += p_e (silu(h Wg[e]) * h Wu[e]) Wd[e];
                        plus the shared experts, x += (silu(h Sg) * h Su) Sd
    logits = (rms(x) * (1 + gf)) Head

in float32.  With ``dtype=jnp.bfloat16`` (the control) weights and every
matmul's output are rounded to bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import weights as weights_lib


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _softmax(s):
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _rope(x, theta):
    """x: (L, heads, hd); rotate halves, position = row."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(x.shape[0], dtype=np.float32)[:, None] * freqs
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = np.split(x, 2, axis=-1)
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Reference:
    """Logits of the configuration's forward, from the seed."""

    def __init__(self, conf: dict, cfg, seed: int, dtype=jnp.float32):
        self.m = conf["model"]
        self.dtype = dtype
        root = weights_lib.root_key(seed)
        self.layers = []
        for gi, group in enumerate(self.m["layers"]):
            draw = weights_lib.layer_fn(cfg, gi)
            for r in range(group["repeat"]):
                period = self._tree(draw(root, r))
                for li, spec in enumerate(group["period"]):
                    self.layers.append((spec, period[f"l{li}"]))
        self.embed, self.ln_f, self.head = (
            self._tree(weights_lib.leaf(cfg, seed, n))
            for n in ("embed", "ln_f_w", "head"))

    def _round(self, x):
        return np.asarray(jnp.asarray(x, self.dtype), np.float32)

    def _tree(self, t):
        return jax.tree.map(self._round, t)

    def _mm(self, a, b, spec=None):
        return self._round(a @ b if spec is None else np.einsum(spec, a, b))

    def _rms(self, x, g):
        x = x / np.sqrt(np.mean(x * x, -1, keepdims=True)
                        + self.m["norm_eps"])
        return self._round(x * (1.0 + g))

    def _ffn(self, h, wg, wu, wd):
        return self._mm(self._round(_silu(self._mm(h, wg)))
                        * self._mm(h, wu), wd)

    def _moe(self, h, p):
        e = self.m["moe"]
        out = np.zeros_like(h)
        for t in range(h.shape[0]):
            s = self._mm(h[t], p["router"])
            s = (1.0 / (1.0 + np.exp(-s)) if e.get("gate") == "sigmoid"
                 else _softmax(s))
            top = np.argsort(-s, kind="stable")[:e["top_k"]]
            for i in top:
                out[t] += (s[i] / s[top].sum()) * self._ffn(
                    h[t], p["w_gate"][i], p["w_up"][i], p["w_down"][i])
        if e.get("num_shared", 0):
            out += self._ffn(h, p["ws_gate"], p["ws_up"], p["ws_down"])
        return self._round(out)

    def _attention(self, h, a):
        m = self.m
        q, k, v = (self._mm(h, a[n], "ld,dhk->lhk")
                   for n in ("wq", "wk", "wv"))
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
        g = m["num_heads"] // m["num_kv_heads"]
        k, v = np.repeat(k, g, axis=1), np.repeat(v, g, axis=1)
        s = np.einsum("qhk,shk->hqs", q, k) / np.sqrt(m["head_dim"])
        L = h.shape[0]
        s = np.where(np.tril(np.ones((L, L), bool)), s, -np.inf)
        o = self._round(np.einsum("hqs,shk->qhk", _softmax(s), v))
        return self._mm(o, a["wo"], "lhk,hkd->ld")

    def _forward(self, tokens, rows):
        x = self.embed[np.asarray(tokens)]
        for spec, p in self.layers:
            x = x + self._attention(self._rms(x, p["ln1_w"]), p["attn"])
            h = self._rms(x, p["ln2_w"])
            if spec.get("mlp", "dense") == "moe":
                x = x + self._moe(h, p["moe"])
            else:
                mp = p["mlp"]
                x = x + self._ffn(h, mp["w_gate"], mp["w_up"], mp["w_down"])
        return self._mm(self._rms(x[rows], self.ln_f), self.head)

    def logits(self, seqs):
        """seqs: list of (tokens, ctx, rows), as reference.Reference takes
        them; one (len(rows), vocab) float32 array per sequence."""
        return [self._forward(t, r) for t, _, r in seqs]
