"""Plain reference forward for dense GQA/MHA decoders, in jax.numpy.

Independent of the program's model code: it reads the configuration file,
draws the same seeded weights (weights.py) one layer at a time, and runs

    x = embed[tokens]
    per layer:  h = rms(x) * (1 + g1)
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
                q, k = rope(q), rope(k)       (half-split pairs, theta)
                x += softmax(q k^T / sqrt(hd) + causal) v  Wo
                h = rms(x) * (1 + g2);  x += (silu(h Wg) * h Wu) Wd
    logits = (rms(x) * (1 + gf)) Head

in float32 at ``highest`` matmul precision.  Query head j reads KV head
j // (heads / kv_heads).

A configuration that states quantized storage is held to it here too:

* ``weight_bits``: every projection, the embedding and the head are rounded
  to ``bits``-bit integers times a power of two, one exponent per 512-wide
  tile of the contraction axis (the whole axis when it is shorter) and
  output channel: e = frexp(amax) - (bits - 1), q = clip(round(w / 2^e)).
  Attention's output projection contracts per head (tiles of head_dim).
* ``kv_bits``: keys (after rope) and values are stored the same way, one
  exponent per page of ``page_size`` positions and KV head, written as the
  paged pool writes them (``quant_pages``): prompt rows at once, then one
  generated position at a time, the page's exponent only ever rising.  A
  query reads earlier pages as their last write left them and its own page
  as it stood when the query's position was written.  Each page starts
  empty (exponent -126).

The control is the same forward in bfloat16 (``dtype=jnp.bfloat16``):
weights, activations and matmul outputs in bfloat16, norms and softmax
computed in float32 and rounded back.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights as weights_lib

ROW_BLOCK = 512          # sequences are padded to a multiple of this
WQ_TILE = 512            # weight-quant contraction tile


# --- quantization, written from the configuration's statement ------------

def _pot_exp(amax, bits):
    """The grid's exponent: amax = m 2^f with m in [0.5, 1), e = f - (bits-1)."""
    _, f = jnp.frexp(amax.astype(jnp.float32))
    return jnp.clip(f - (bits - 1), -126, 126).astype(jnp.float32)


def _pow2(e):
    """Exactly 2^e (jnp.exp2 is a polynomial on some backends)."""
    return jnp.ldexp(jnp.ones_like(e, jnp.float32), e.astype(jnp.int32))


def _pot_round(x, amax, bits):
    qmax = 2 ** (bits - 1) - 1
    scale = _pow2(_pot_exp(amax, bits))[..., None]
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def quant_weight(w, bits, axis):
    """Round `w` along contraction axis `axis` in tiles of <= 512."""
    k = w.shape[axis]
    t = k if k <= WQ_TILE else math.gcd(k, WQ_TILE)
    x = jnp.moveaxis(w.astype(jnp.float32), axis, -1)
    lead = x.shape[:-1]
    x = x.reshape(lead + (k // t, t))
    q = _pot_round(x, jnp.max(jnp.abs(x), axis=-1), bits)
    return jnp.moveaxis(q.reshape(lead + (k,)), -1, axis)


def quant_pages(x, bits, page, ctx, length):
    """Keys or values as the paged pool holds them, token by token.

    x: (L, kvh, hd), L a page multiple.  The prefill writes each page's
    prompt rows (positions < ctx) at once, at the exponent of those rows;
    then every generated position t raises its page's exponent to
    max(e, e(t)), shifts the resident integers down by the rise (rounding
    half up) and stores t at the new exponent.  Returns the pages as the
    last write left them (L, kvh, hd) and, for every position t, its own
    page as it stood right after t was written (L, page, kvh, hd)."""
    L, kvh, hd = x.shape
    P = L // page
    qmax = 2 ** (bits - 1) - 1
    xb = x.astype(jnp.float32).reshape(P, page, kvh, hd)
    pos = jnp.arange(L).reshape(P, page)
    pre = (pos < ctx)[:, :, None, None]
    amax = jnp.max(jnp.where(pre, jnp.abs(xb), 0.0), axis=(1, 3))
    e0 = jnp.where(jnp.any(pre[:, :, 0, 0], 1)[:, None], _pot_exp(amax, bits),
                   -126.0)

    def rnd(v, e):
        return jnp.clip(jnp.round(v * _pow2(-e)), -qmax, qmax)

    q0 = jnp.where(pre, rnd(xb, e0[:, None, :, None]), 0.0)

    def step(carry, j):
        q, e = carry
        write = ((pos[:, j] >= ctx) & (pos[:, j] < length))[:, None]
        xt = xb[:, j]
        e_new = jnp.where(write, jnp.maximum(e, _pot_exp(
            jnp.max(jnp.abs(xt), -1), bits)), e)
        # a shift of 31 or more empties any stored integer
        d = jnp.minimum(e_new - e, 31.0)[:, None, :, None]
        q = jnp.where(d > 0, jnp.clip(
            jnp.floor((q + _pow2(d - 1)) * _pow2(-d)), -qmax, qmax), q)
        q = q.at[:, j].set(jnp.where(write[:, :, None],
                                     rnd(xt, e_new[:, :, None]), q[:, j]))
        return (q, e_new), q * _pow2(e_new)[:, None, :, None]

    _, views = jax.lax.scan(step, (q0, e0), jnp.arange(page))
    final = views[-1].reshape(L, kvh, hd)
    own = views[pos % page, pos // page]              # (P, page, page,..)
    return final, own.reshape(L, page, kvh, hd)


# --- the forward ---------------------------------------------------------

def _rms(x, g, eps, dtype):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * (1.0 + g.astype(jnp.float32))).astype(dtype)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attend(q, k, v, dtype, own=None, page=None):
    """q: (L, h, hd), k/v: (L, kvh, hd); causal, in query blocks.  With
    `own` ((L, page, kvh, hd) keys and values), each query reads its own
    page from there and only earlier pages from k/v."""
    L, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    nb = L // ROW_BLOCK
    qb = q.reshape(nb, ROW_BLOCK, kvh, g, hd)
    kpos = jnp.arange(L)

    def block(args):
        i, qi = args[0], args[1]
        qpos = i * ROW_BLOCK + jnp.arange(ROW_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", qi, k).astype(jnp.float32)
        if own is None:
            s = jnp.where(qpos[:, None] >= kpos[None], s / math.sqrt(hd),
                          -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(dtype)
            return jnp.einsum("kgqs,skd->qkgd", p, v).reshape(ROW_BLOCK, h,
                                                            hd)
        ko, vo = args[2], args[3]
        far = (kpos[None] // page) < (qpos[:, None] // page)
        s = jnp.where(far, s / math.sqrt(hd), -jnp.inf)
        so = jnp.einsum("qkgd,qskd->kgqs", qi, ko).astype(jnp.float32)
        mine = jnp.arange(page)[None] <= (qpos[:, None] % page)
        so = jnp.where(mine, so / math.sqrt(hd), -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([s, so], -1), -1).astype(dtype)
        o = (jnp.einsum("kgqs,skd->qkgd", p[..., :L], v)
             + jnp.einsum("kgqs,qskd->qkgd", p[..., L:], vo))
        return o.reshape(ROW_BLOCK, h, hd)

    xs = (jnp.arange(nb), qb)
    if own is not None:
        xs = xs + tuple(t.reshape(nb, ROW_BLOCK, page, kvh, hd) for t in own)
    return jax.lax.map(block, xs).reshape(L, h, hd)


def _layer(lp, x, pos, ctx, length, *, m, kv_bits, page, dtype):
    eps, theta = m["norm_eps"], m["rope_theta"]
    h = _rms(x, lp["ln1_w"], eps, dtype)
    a = lp["attn"]
    q = jnp.einsum("ld,dhk->lhk", h, a["wq"])
    k = jnp.einsum("ld,dhk->lhk", h, a["wk"])
    v = jnp.einsum("ld,dhk->lhk", h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    if kv_bits is None:
        o = _attend(q, k, v, dtype)
    else:
        (kf, ko), (vf, vo) = (quant_pages(t, kv_bits, page, ctx, length)
                              for t in (k, v))
        o = _attend(q, kf.astype(dtype), vf.astype(dtype), dtype,
                    own=(ko.astype(dtype), vo.astype(dtype)), page=page)
    x = x + jnp.einsum("lhk,hkd->ld", o, a["wo"]).astype(dtype)
    h = _rms(x, lp["ln2_w"], eps, dtype)
    mp = lp["mlp"]
    u = jax.nn.silu(h @ mp["w_gate"]) * (h @ mp["w_up"])
    return x + (u @ mp["w_down"]).astype(dtype)


_AXES = {"wq": 0, "wk": 0, "wv": 0, "wo": 1, "w_gate": 0, "w_up": 0,
         "w_down": 0}


def _prepare(tree, wbits, dtype):
    """Quantize (when the configuration states it) and cast one layer."""
    def one(kp, w):
        name = str(getattr(kp[-1], "key", kp[-1]))
        if wbits is not None and name in _AXES:
            w = quant_weight(w, wbits, _AXES[name])
        return w.astype(dtype)
    return jax.tree_util.tree_map_with_path(one, tree)


class Reference:
    """Logits of one configuration's reference forward, from the seed."""

    def __init__(self, conf: dict, cfg, seed: int, dtype=jnp.float32):
        self.conf, self.cfg, self.seed, self.dtype = conf, cfg, seed, dtype
        self.m = conf["model"]
        e = conf["engine"]
        self.wbits, self.kv_bits, self.page = (e["weight_bits"], e["kv_bits"],
                                               e["page_size"])
        draw = weights_lib.layer_fn(cfg)
        self._root = weights_lib.root_key(seed)
        self._params = jax.jit(lambda root, r: _prepare(
            draw(root, r)["l0"], self.wbits, dtype))
        layer = functools.partial(_layer, m=self.m, kv_bits=self.kv_bits,
                                  page=self.page, dtype=dtype)
        # one sequence at a time through one compiled layer
        self._layers = jax.jit(lambda lp, x, pos, ctx, length: jax.lax.map(
            lambda a: layer(lp, a[0], pos, a[1], a[2]), (x, ctx, length)))

    def _layer_params(self, r):
        return self._params(self._root, r)

    def _vocab(self, name, axis):
        w = weights_lib.leaf(self.cfg, self.seed, name)
        if self.wbits is not None:
            w = jax.jit(quant_weight, static_argnums=(1, 2))(
                w, self.wbits, axis)
        return w.astype(self.dtype)

    def logits(self, seqs):
        """seqs: list of (tokens, ctx, rows): int token ids, the prompt
        length the engine prefilled (prompt - 1), and the positions whose
        next-token logits are wanted.  Returns one (len(rows), vocab)
        float32 array per sequence."""
        precision = "highest" if self.dtype == jnp.float32 else "default"
        Lp = -(-max(len(t) for t, _, _ in seqs) // ROW_BLOCK) * ROW_BLOCK
        toks = np.zeros((len(seqs), Lp), np.int32)
        for i, (t, _, _) in enumerate(seqs):
            toks[i, :len(t)] = t
        ctx = jnp.asarray([c for _, c, _ in seqs], jnp.int32)
        length = jnp.asarray([len(t) for t, _, _ in seqs], jnp.int32)
        pos = jnp.arange(Lp, dtype=jnp.int32)
        with jax.default_matmul_precision(precision):
            x = jnp.take(self._vocab("embed", 1), jnp.asarray(toks), axis=0)
            for r in range(self.m["num_layers"]):
                lp = self._layer_params(r)
                x = self._layers(lp, x, pos, ctx, length)
                del lp
            b = np.concatenate([np.full(len(rows), i)
                                for i, (_, _, rows) in enumerate(seqs)])
            rows = np.concatenate([rows for _, _, rows in seqs])
            h = _rms(x[jnp.asarray(b), jnp.asarray(rows)],
                     weights_lib.leaf(self.cfg, self.seed, "ln_f_w"),
                     self.m["norm_eps"], self.dtype)
            lg = np.asarray((h @ self._vocab("head", 0)).astype(jnp.float32))
        return np.split(lg, np.cumsum([len(r) for _, _, r in seqs])[:-1])
