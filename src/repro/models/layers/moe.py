"""Token-dropping top-k Mixture-of-Experts.

Two execution paths:

* **mesh path (shard_map)** — used whenever a mesh with a "model" axis is
  in context (production). Token routing/dispatch is *device-local* (each
  data shard scatters only its own tokens), which eliminates the
  catastrophic GSPMD behavior of a jit-level scatter (the baseline
  dry-run measured 1.6 TB/device peak and a 2133 s collective term for
  qwen3-moe train_4k — see EXPERIMENTS §Perf). Expert placement adapts:
    - E >= model-extent (qwen3: 128/16): experts sharded over "model",
      each shard runs its expert slice on the tokens routed to it;
    - E <  model-extent (mixtral: 8/16): experts replicated, the FFN
      hidden dim shards over "model" (partial products).
  A single bf16 psum over "model" combines per-token outputs in both
  layouts.

* **dense path (pure jit)** — no mesh (CPU smoke tests, single device):
  the original sort-based dispatch.

Both paths drop tokens past static capacity C = ceil(T_local * k / E * cf)
and return the Switch-style load-balancing aux loss.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import maybe_shard
from repro.models.layers.common import COMPUTE_DTYPE, PARAM_DTYPE, Params, Specs


class MoEOut(NamedTuple):
    y: jnp.ndarray
    aux_loss: jnp.ndarray
    router_probs: jnp.ndarray  # (T, E) — consumed by the HAP expert-affinity hook


def moe_init(key, d_model: int, d_ff: int, n_experts: int
             ) -> tuple[Params, Specs]:
    kr, kg, ku, kd = jax.random.split(key, 4)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)
    p = {
        "router": jax.random.normal(kr, (d_model, n_experts), PARAM_DTYPE)
        * scale_in,
        "gate": jax.random.normal(kg, (n_experts, d_model, d_ff), PARAM_DTYPE)
        * scale_in,
        "up": jax.random.normal(ku, (n_experts, d_model, d_ff), PARAM_DTYPE)
        * scale_in,
        "down": jax.random.normal(kd, (n_experts, d_ff, d_model), PARAM_DTYPE)
        * scale_out,
    }
    # Expert dim shards over "model" only when it can divide the 16-way
    # production axis (qwen3: 128 experts); small-expert MoEs (mixtral: 8)
    # shard the FFN hidden dim instead — matching the shard_map layouts in
    # _moe_sharded. The free dim additionally shards over "data"
    # (FSDP-style): expert weights dominate total params, and leaving them
    # data-replicated put mixtral at 1.6 TB/device (EXPERIMENTS §Perf).
    if n_experts >= 16:
        s_gate = P("model", None, "data")
        s_down = P("model", "data", None)
    else:
        s_gate = P(None, "data", "model")
        s_down = P(None, "model", "data")
    s = {
        "router": P(None, None),
        "gate": s_gate,
        "up": s_gate,
        "down": s_down,
    }
    return p, s


# ------------------------------------------------------------ local core
def _route_and_dispatch(xt, router, top_k, e_total, e_lo, e_loc, cap):
    """Device-local routing: returns (buf (e_loc, cap, D), combine info).

    Chooses top_k experts per token from the FULL router, keeps the choices
    that fall in this shard's expert range [e_lo, e_lo + e_loc), ranks them
    within expert (stable sort), drops past ``cap``.
    """
    t, d = xt.shape
    logits = xt.astype(jnp.float32) @ router                 # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    flat_e = top_i.reshape(-1)
    mine = (flat_e >= e_lo) & (flat_e < e_lo + e_loc)
    local_e = jnp.where(mine, flat_e - e_lo, e_loc)          # e_loc = trash
    order = jnp.argsort(local_e, stable=True)
    sorted_e = local_e[order]
    counts = jax.ops.segment_sum(jnp.ones_like(local_e), local_e,
                                 num_segments=e_loc + 1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * top_k) - starts[sorted_e]
    keep = (sorted_e < e_loc) & (rank < cap)
    dest = jnp.where(keep, sorted_e * cap + rank, e_loc * cap)
    src = order // top_k
    buf = jnp.zeros((e_loc * cap + 1, d), xt.dtype).at[dest].set(xt[src])
    buf = buf[:-1].reshape(e_loc, cap, d)
    inv = jnp.zeros((t * top_k,), jnp.int32).at[order].set(
        jnp.where(keep, dest, e_loc * cap).astype(jnp.int32))
    return buf, (inv, top_w, probs, flat_e)


def _combine(out_buf, inv, top_w, t, top_k):
    e_loc, cap, d = out_buf.shape
    out_flat = jnp.concatenate(
        [out_buf.reshape(e_loc * cap, d), jnp.zeros((1, d), out_buf.dtype)])
    per_choice = out_flat[inv].reshape(t, top_k, d)
    w = top_w.astype(per_choice.dtype)[..., None]
    return jnp.sum(per_choice * w, axis=1)


def _ffn(w_gate, w_up, w_down, h):
    act = jax.nn.silu(h @ w_gate.astype(h.dtype)) * (h @ w_up.astype(h.dtype))
    return act @ w_down.astype(h.dtype)


def _aux(probs, flat_e, t, top_k, e_total, data_axes=None):
    frac = jax.ops.segment_sum(
        jnp.ones((t * top_k,)) / (t * top_k), flat_e, num_segments=e_total)
    mean_prob = jnp.mean(probs, axis=0)
    if data_axes:
        frac = jax.lax.pmean(frac, data_axes)
        mean_prob = jax.lax.pmean(mean_prob, data_axes)
    return e_total * jnp.sum(frac * mean_prob)


# ------------------------------------------------------------- dense path
def _moe_dense(p: Params, x: jnp.ndarray, *, top_k: int,
               capacity_factor: float) -> MoEOut:
    b, s, d = x.shape
    e = p["router"].shape[-1]
    t = b * s
    cap = max(4, int(math.ceil(t * top_k / e * capacity_factor)))
    xt = x.reshape(t, d)
    buf, (inv, top_w, probs, flat_e) = _route_and_dispatch(
        xt, p["router"], top_k, e, 0, e, cap)
    buf = maybe_shard(buf, P("model", None, None))
    out_buf = jax.vmap(_ffn)(p["gate"], p["up"], p["down"], buf)
    y = _combine(out_buf, inv, top_w, t, top_k).reshape(b, s, d)
    aux = _aux(probs, flat_e, t, top_k, e)
    return MoEOut(y.astype(x.dtype), aux.astype(jnp.float32), probs)


# -------------------------------------------------------------- mesh path
def _moe_sharded(p: Params, x: jnp.ndarray, *, top_k: int,
                 capacity_factor: float, mesh_axes) -> MoEOut:
    e = p["router"].shape[-1]
    d_ff = p["gate"].shape[-1]
    model_ext = mesh_axes["model"]
    data_axes = tuple(a for a in ("pod", "data") if a in mesh_axes)
    expert_parallel = e % model_ext == 0
    mesh = jax.sharding.get_abstract_mesh()

    if expert_parallel:
        gspec = P("model", None, None)
        dspec = P("model", None, None)
    else:
        if d_ff % model_ext:
            return _moe_dense(p, x, top_k=top_k,
                              capacity_factor=capacity_factor)
        gspec = P(None, None, "model")      # shard FFN hidden dim
        dspec = P(None, "model", None)

    dd = data_axes if data_axes else None
    x_spec = P(dd, None, None)

    def body(x_loc, router, gate, up, down):
        b, s, d = x_loc.shape
        t = b * s
        if expert_parallel:
            e_loc = gate.shape[0]
            e_lo = jax.lax.axis_index("model") * e_loc
        else:
            e_loc, e_lo = e, 0
        cap = max(4, int(math.ceil(t * top_k / e * capacity_factor)))
        xt = x_loc.reshape(t, d)
        buf, (inv, top_w, probs, flat_e) = _route_and_dispatch(
            xt, router, top_k, e, e_lo, e_loc, cap)
        out_buf = jax.vmap(_ffn)(gate, up, down, buf)
        y_part = _combine(out_buf, inv, top_w, t, top_k)
        # expert-parallel: sums each token's k shard-local expert outputs;
        # ffn-parallel: sums the hidden-dim partial products. One psum.
        y = jax.lax.psum(y_part, "model")
        aux = _aux(probs, flat_e, t, top_k, e, data_axes)
        probs_out = probs.reshape(b, s, e)
        return y.reshape(b, s, d), aux, probs_out

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), gspec, gspec, dspec),
        out_specs=(x_spec, P(), P(dd, None, None)),
    )
    y, aux, probs = fn(x, p["router"], p["gate"], p["up"], p["down"])
    return MoEOut(y.astype(x.dtype), aux.astype(jnp.float32),
                  probs.reshape(-1, e))


def moe_apply(p: Params, x: jnp.ndarray, *, top_k: int,
              capacity_factor: float = 1.25) -> MoEOut:
    """x: (B, S, D) -> (B, S, D). Dispatches on mesh context."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and not mesh.empty and "model" in mesh.axis_names:
        return _moe_sharded(p, x, top_k=top_k,
                            capacity_factor=capacity_factor,
                            mesh_axes=dict(mesh.shape))
    return _moe_dense(p, x, top_k=top_k, capacity_factor=capacity_factor)
