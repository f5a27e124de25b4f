"""Seeded weights, made on the device in one jitted call.

Every leaf is drawn from its own key, ``fold_in(root(seed), crc32(path))``,
and a leaf stacked over a group's layers draws layer ``r`` from
``fold_in(leaf_key, r)``.  So one layer's weights can be drawn alone and
equal that layer's slice of the whole tree: the reference draws the model
layer by layer, the serving engine gets the tree at once.

Scales keep every sub-layer's output near unit size, so the residual stream
stays O(1) through the stack and the logits are about N(0, 1): an embedding
of unit normals, projections at 1/sqrt(fan-in), norm gains 1 + N(0, 0.1^2),
and QKV biases N(0, 0.25^2) so that the bias path matters.  An expert's
projections are scaled by their own contraction axis, not the expert axis
they are stacked on; the router and the shared experts like any projection,
so router logits are about N(0, 1) too.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def _fan_in(name: str, shape, cfg) -> float:
    if name == "embed":
        return 1.0
    if name.endswith("_w"):
        return 1.0 / 0.1 ** 2          # norm gains
    if name in ("bq", "bk", "bv"):
        return 1.0 / 0.25 ** 2
    if name == "wo":
        return cfg.num_heads * cfg.head_dim
    if name in ("wq", "wk", "wv", "head", "router", "ws_gate", "ws_up",
                "ws_down"):
        return shape[0]
    if name in ("w_gate", "w_up", "w_down"):
        # (in, out), or (experts, in, out) stacked over a layer's experts
        return shape[-2]
    raise ValueError(f"no scale for parameter {name!r}")


def root_key(seed: int) -> jax.Array:
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _leaf_key(root, path: str):
    return jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, name, shape, cfg, dtype):
    std = 1.0 / math.sqrt(_fan_in(name, shape, cfg))
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def tree_shapes(cfg):
    """The program's parameter tree as shapes (nothing is allocated)."""
    from repro.models import lm
    return jax.eval_shape(
        lambda: lm.init_lm(cfg, jax.random.PRNGKey(0), jnp.float32)[0])


def make(cfg, seed: int, dtype=jnp.float32):
    """The whole parameter tree, on the default device."""
    shapes = tree_shapes(cfg)

    def build(root):
        def leaf(kp, s):
            path = _path(kp)
            name = path.rsplit("/", 1)[-1]
            key = _leaf_key(root, path)
            if path.startswith("group"):
                keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(
                    jnp.arange(s.shape[0]))
                return jax.vmap(
                    lambda k: _draw(k, name, s.shape[1:], cfg, dtype))(keys)
            return _draw(key, name, s.shape, cfg, dtype)

        return jax.tree_util.tree_map_with_path(leaf, shapes)

    # the seed enters as data, so every seed runs the one cached program
    return jax.jit(build)(root_key(seed))


def layer_fn(cfg, group: int = 0, dtype=jnp.float32):
    """A jitted ``f(root, r)``: repetition ``r`` of group `group`'s period,
    every layer of it (``l0`` ... ``lk``), as the slice of `make`'s stacked
    leaves."""
    shapes = tree_shapes(cfg)[f"group{group}"]

    def draw(root, r):
        def leaf(kp, s):
            path = f"group{group}/" + _path(kp)
            key = jax.random.fold_in(_leaf_key(root, path), r)
            return _draw(key, path.rsplit("/", 1)[-1], s.shape[1:], cfg,
                         dtype)
        return jax.tree_util.tree_map_with_path(leaf, shapes)

    return jax.jit(draw)


def leaf(cfg, seed: int, name: str, dtype=jnp.float32):
    """One top-level (unstacked) leaf of `make`'s tree, drawn alone."""
    s = tree_shapes(cfg)[name]
    return jax.jit(lambda root: _draw(_leaf_key(root, name), name, s.shape,
                                      cfg, dtype))(root_key(seed))
