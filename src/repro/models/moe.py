"""Mixture-of-experts block: top-k routing, capacity dispatch, manual EP.

Dispatch is GShard-style GROUP-WISE (one group per sequence, per-group
expert capacity, overflow dropped): tokens are ranked within their expert
by a cumulative count, gathered into (E, C, d) buffers, pushed through
the expert SwiGLUs as one batched einsum, and combined back weighted by
router probs. Shared experts (qwen2-moe) run densely on every token.

Distribution: two code paths with IDENTICAL numerics —

* **pure path** (no active ShardingPlan; smoke tests, single device):
  plain jnp over (G, T, d).
* **manual-EP path** (active plan): the block runs under ``shard_map``.
  Tokens are sharded over the data axes and replicated over 'model', so
  each device dispatches its local groups, computes ONLY its expert slice
  (experts sharded over 'model' when E divides it — phi3.5 — otherwise
  the per-expert ff dim is sharded — qwen2-moe), writes the slice into
  the group-local combine buffer with a dynamic_update_slice, gathers
  per-token results locally, and a single ``psum`` over 'model' merges
  expert (or ff-partial) contributions. No GSPMD scatter decisions —
  the gather/scatter that made the partitioner replicate 34 GB buffers
  is now device-local by construction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs import ModelConfig
from ..parallel import active_plan
from .layers import dense_init, mlp_forward, mlp_init


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, cfg.d_ff, m.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "moe_gate": dense_init(ks[1], (e, d, f), dtype, in_axis=1),
        "moe_up": dense_init(ks[2], (e, d, f), dtype, in_axis=1),
        "moe_down": dense_init(ks[3], (e, f, d), dtype, in_axis=1),
    }
    if m.n_shared_experts:
        sf = m.shared_d_ff * m.n_shared_experts
        sh = mlp_init(ks[4], cfg, dtype, d_ff=sf)
        p.update({"sh_gate": sh["w_gate"], "sh_up": sh["w_up"],
                  "sh_down": sh["w_down"]})
    return p


# ---------------------------------------------------------------------------
# Core (device-local or single-device) dispatch + expert compute + combine
# ---------------------------------------------------------------------------
def _dispatch(x, router, cfg: ModelConfig):
    """Per-group top-k routing. x: (g, t, d) -> (dest, weights, cap)."""
    m = cfg.moe
    g, t, _ = x.shape
    e, k = m.n_experts, m.top_k
    logits = x.astype(jnp.float32) @ router                  # (g, t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                   # (g, t, k)
    top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)

    cap = int(max(k * t * m.capacity_factor // e, 1))
    ef = top_e.reshape(g, t * k)
    onehot = jax.nn.one_hot(ef, e, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot
    rank = jnp.take_along_axis(pos_in_e, ef[..., None], axis=2)[..., 0]
    keep = rank < cap
    dest = jnp.where(keep, ef * cap + rank, e * cap)         # (g, t*k)
    w = (top_p * keep.reshape(g, t, k))                      # (g, t, k)
    return dest, w, cap


def _dispatch_buffers(x, dest, cap: int, e: int, k: int):
    """Scatter routed token copies into (g, e, cap, d) expert buffers."""
    g, t, d = x.shape
    gid = jnp.arange(g)[:, None]
    xk = jnp.repeat(x, k, axis=1)                            # (g, t*k, d)
    buf = jnp.zeros((g, e * cap + 1, d), x.dtype)
    buf = buf.at[gid, dest].set(xk)
    return buf[:, :-1].reshape(g, e, cap, d)


def _expert_mlp(xe, p):
    """Batched SwiGLU over (g, e_n, cap, d) with local weight slices."""
    h = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["moe_gate"]))
         * jnp.einsum("gecd,edf->gecf", xe, p["moe_up"]))
    return jnp.einsum("gecf,efd->gecd", h, p["moe_down"])


def _combine(ye_full, dest, weights, cap: int):
    """(g, e, cap, d) expert outputs -> per-token weighted sum."""
    g, e, _, d = ye_full.shape
    t, k = weights.shape[1], weights.shape[2]
    yflat = jnp.concatenate(
        [ye_full.reshape(g, e * cap, d).astype(jnp.float32),
         jnp.zeros((g, 1, d), jnp.float32)], axis=1)
    yk = jnp.take_along_axis(yflat, dest[..., None], axis=1)  # dropped -> 0
    yk = yk.reshape(g, t, k, d)
    return jnp.einsum("gtkd,gtk->gtd", yk, weights.astype(jnp.float32))


def _expert_core(x, p, cfg: ModelConfig, e_lo: int, e_n: int, cap: int,
                 dest, weights):
    """Dispatch -> local experts [e_lo, e_lo+e_n) -> combine (psum path).

    Weights p['moe_*'] hold only the local expert slice (e_n experts,
    possibly ff-partial). Returns the (partial) output (g, t, d).
    """
    m = cfg.moe
    g, t, d = x.shape
    e = m.n_experts
    xe = jax.lax.dynamic_slice_in_dim(
        _dispatch_buffers(x, dest, cap, e, m.top_k), e_lo, e_n, axis=1)
    ye = _expert_mlp(xe, p)                                  # (g, e_n, cap, d)
    yfull = jnp.zeros((g, e * cap, d), ye.dtype)
    yfull = jax.lax.dynamic_update_slice_in_dim(
        yfull, ye.reshape(g, e_n * cap, d), e_lo * cap, axis=1)
    return _combine(yfull.reshape(g, e, cap, d), dest, weights, cap)


def _aux_loss(x, router, cfg: ModelConfig):
    """Switch load-balance loss: E * sum_e f_e * P_e (plain jnp, global)."""
    m = cfg.moe
    e, k = m.n_experts, m.top_k
    logits = x.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_e = jax.lax.top_k(probs, k)
    pe = probs.mean(axis=(0, 1))
    fe = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0) / (top_e.size)
    return e * jnp.sum(fe * pe)


# ---------------------------------------------------------------------------
# Public block
# ---------------------------------------------------------------------------
def moe_forward(p, x, cfg: ModelConfig):
    """x: (G, T, d) -> (y, aux_loss). Groups = sequences (data-sharded)."""
    m = cfg.moe
    plan = active_plan()
    aux = _aux_loss(x, p["router"], cfg)

    routed = {k: p[k] for k in ("moe_gate", "moe_up", "moe_down")}
    if plan is None or plan.rules.get("batch") is None:
        dest, w, cap = _dispatch(x, p["router"], cfg)
        y = _expert_core(x, routed, cfg, 0, m.n_experts, cap, dest, w)
    else:
        y = _moe_shard_map(routed, p["router"], x, cfg, plan)

    y = y.astype(x.dtype)
    if m.n_shared_experts:
        y = y + mlp_forward({"w_gate": p["sh_gate"], "w_up": p["sh_up"],
                             "w_down": p["sh_down"]}, x)
    return y, aux


def _moe_shard_map(routed, router, x, cfg: ModelConfig, plan):
    """Manual expert parallelism. Two exchange schedules:

    * **psum path** (tokens replicated over 'model'): every device
      dispatches the full sequence, computes its expert slice, one psum
      merges. Simple, but the psum carries the FULL residual stream.
    * **all-to-all path** (tokens sequence-sharded over 'model' — the
      sequence-parallel prefill/train plans): each device dispatches its
      sequence slice into (g, E, C, d) buffers; ``lax.all_to_all`` over
      'model' exchanges expert shards (the paper's All-to-All pattern,
      GShard-style); only ROUTED TOKEN BUFFERS cross the wire —
      ~(k*cf/msize) of the psum path's bytes.
    """
    m = cfg.moe
    batch_axes = plan.rules["batch"]
    e_axis = plan.rules.get("experts")          # 'model' or None
    f_axis = plan.rules.get("ff")               # 'model' or None
    model_axis = e_axis or f_axis
    msize = plan.axis_size(model_axis) if model_axis else 1
    e = m.n_experts
    e_n = e // plan.axis_size(e_axis) if e_axis else e
    seq_ax = plan.rules.get("seq")
    a2a = bool(e_axis) and seq_ax == model_axis and msize > 1 \
        and x.shape[1] % msize == 0

    w_specs = {"moe_gate": P(e_axis, None, f_axis),
               "moe_up": P(e_axis, None, f_axis),
               "moe_down": P(e_axis, f_axis, None)}
    x_spec = P(batch_axes, model_axis if a2a else None, None)

    def local(weights, router_l, x_l):
        e_lo = jax.lax.axis_index(e_axis) * e_n if e_axis else 0
        dest, w, cap = _dispatch(x_l, router_l, cfg)
        if a2a:
            xe = _dispatch_buffers(x_l, dest, cap, e, m.top_k)
            # exchange expert shards: (g, E, C, d) -> (g, E/m, m*C, d)
            xr = jax.lax.all_to_all(xe, model_axis, split_axis=1,
                                    concat_axis=2, tiled=True)
            ye = _expert_mlp(xr, weights).astype(x_l.dtype)
            back = jax.lax.all_to_all(ye, model_axis, split_axis=2,
                                      concat_axis=1, tiled=True)
            return _combine(back, dest, w, cap).astype(x_l.dtype)
        y = _expert_core(x_l, weights, cfg, e_lo, e_n, cap, dest, w)
        if model_axis:
            y = jax.lax.psum(y.astype(x_l.dtype), model_axis)
        return y

    fn = jax.shard_map(local, mesh=plan.mesh,
                       in_specs=(w_specs, P(None, None), x_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(routed, router, x)
