"""Mixture-of-Experts: top-k dropping router with sort-based dispatch and
expert parallelism.

Dispatch is production-grade (no one-hot einsum blowup): token->expert pairs
are sorted by expert id, packed into a dense (E_local, capacity, D) buffer
(drops beyond capacity, standard Switch semantics), run through stacked
expert FFNs with a single batched einsum, and scattered back weighted by the
(optionally renormalized) router probabilities.

Expert parallelism: `moe_apply` takes (e_start, e_count) — the slice of
experts this shard owns — and an optional `psum_axis`.  Tokens are replicated
across the model axis between TP ops (megatron convention), so each shard
routes all its local tokens, computes only its own experts, and the final
psum over the model axis combines expert outputs — EP without any all_to_all
(DESIGN.md §3.1).  deepseek-style shared experts and aux load-balance loss
included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.models.layers import make_dense


def make_moe(key, d_model: int, cfg: MoEConfig, mlp_kind: str):
    ks = jax.random.split(key, 5)
    E, F = cfg.n_experts, cfg.d_expert
    gated = mlp_kind in ("silu", "geglu")
    scale = d_model ** -0.5
    p = {
        "router": {"w": jax.random.normal(ks[0], (d_model, E), jnp.float32) * scale},
        "up": jax.random.normal(ks[1], (E, d_model, F), jnp.float32) * scale,
        "down": jax.random.normal(ks[2], (E, F, d_model), jnp.float32) * (F ** -0.5),
    }
    if gated:
        p["gate"] = jax.random.normal(ks[3], (E, d_model, F), jnp.float32) * scale
    if cfg.n_shared:
        from repro.models.layers import make_mlp
        p["shared"] = make_mlp(ks[4], d_model, cfg.n_shared * F, mlp_kind)
    return p


def _expert_ffn(p, xe, mlp_kind, dtype):
    """xe: (E_local, C, D) -> (E_local, C, D), batched over experts."""
    up = jnp.einsum("ecd,edf->ecf", xe, p["up"].astype(dtype))
    if mlp_kind == "silu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["gate"].astype(dtype))) * up
    elif mlp_kind == "geglu":
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, p["gate"].astype(dtype))) * up
    elif mlp_kind == "gelu":
        h = jax.nn.gelu(up)
    elif mlp_kind == "sqrelu":
        h = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(mlp_kind)
    return jnp.einsum("ecf,efd->ecd", h, p["down"].astype(dtype))


def moe_apply(p, x, cfg: MoEConfig, mlp_kind: str, *, e_start=0, e_count=None,
              psum_axis=None, slice_params=None, dropless=False):
    """x: (..., D).  Returns (y, aux_loss).

    e_start/e_count select the local expert slice (expert parallelism);
    slice_params optionally maps full expert arrays -> local slices (used
    under shard_map where params arrive pre-sliced: pass identity).
    dropless=True sizes the capacity for the worst case (decode steps must
    not drop tokens — a dropped route changes logits).
    """
    E = cfg.n_experts
    e_count = E if e_count is None else e_count
    dtype = x.dtype
    lead = x.shape[:-1]
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    k = cfg.top_k

    # --- routing (fp32) ----------------------------------------------------
    logits = (xf.astype(jnp.float32) @ p["router"]["w"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # (N, E)
    top_p, top_e = jax.lax.top_k(probs, k)                        # (N, k)
    if cfg.normalize_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (computed on the *global* assignment)
    me = probs.mean(axis=0)                                        # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (N * k)
    aux = cfg.aux_loss_weight * E * jnp.sum(me * ce)

    # --- local dispatch ------------------------------------------------------
    C = N if dropless else max(1, int(k * N * cfg.capacity_factor / E))
    ef = top_e.reshape(-1)                                         # (N*k,)
    tf = jnp.repeat(jnp.arange(N), k)
    wf = top_p.reshape(-1).astype(dtype)
    local = (ef >= e_start) & (ef < e_start + e_count)
    le = jnp.where(local, ef - e_start, e_count)                   # non-local -> bucket E_local
    order = jnp.argsort(le, stable=True)
    le_s, tok_s, w_s = le[order], tf[order], wf[order]
    starts = jnp.searchsorted(le_s, jnp.arange(e_count + 1))       # run starts
    pos = jnp.arange(N * k) - starts[le_s]
    keep = (le_s < e_count) & (pos < C)
    slot = jnp.where(keep, le_s * C + pos, e_count * C)            # dump slot at end

    buf = jnp.zeros((e_count * C + 1, D), dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xf[tok_s], 0))
    xe = buf[:-1].reshape(e_count, C, D)

    if slice_params is None:
        # default: slice the local expert range out of full arrays
        slice_params = lambda a: jax.lax.dynamic_slice_in_dim(a, e_start, e_count, 0)
    pl = {kk: slice_params(p[kk]) for kk in ("up", "down", "gate") if kk in p}
    ye = _expert_ffn(pl, xe, mlp_kind, dtype).reshape(-1, D)       # (E_local*C, D)

    contrib = jnp.where(keep[:, None], ye[jnp.minimum(slot, e_count * C - 1)]
                        * w_s[:, None], 0)
    y = jnp.zeros((N, D), dtype).at[tok_s].add(contrib)

    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)

    # shared experts run on every token (replicated across shards)
    if "shared" in p:
        from repro.models.layers import mlp as dense_mlp
        y = y + dense_mlp(p["shared"], xf, mlp_kind, dtype)

    return y.reshape(*lead, D), aux


def moe_apply_auto(p, x, cfg: MoEConfig, mlp_kind: str, *, dropless=False):
    """MoE with automatic expert parallelism.

    When a parallel context is active (launch/train, dry-run) and the expert
    count divides the TP axis, the dispatch runs as a `shard_map` island:
    each (data x model) shard routes its *local* tokens over its *local*
    expert slice and a psum over the model axis combines expert outputs.
    This keeps the sort-based dispatch local — GSPMD would otherwise turn
    the argsort into a distributed sort.  Outside a parallel context this
    is exactly `moe_apply`.
    """
    from repro.distributed.context import get_parallel

    ctx = get_parallel()
    E = cfg.n_experts
    if ctx is None:
        return moe_apply(p, x, cfg, mlp_kind, dropless=dropless)
    mesh = ctx.mesh
    tp = mesh.shape[ctx.tp_axis]
    dp = int(np.prod([mesh.shape[a] for a in ctx.dp_axes]))
    B = x.shape[0]
    if E % tp or B % dp:
        return moe_apply(p, x, cfg, mlp_kind, dropless=dropless)
    e_count = E // tp
    P_ = jax.sharding.PartitionSpec
    dp_axes = ctx.dp_axes
    tp_axis = ctx.tp_axis

    def pspec(path, leaf):
        ps = jax.tree_util.keystr(path)
        if "router" in ps or "shared" in ps:
            return P_(*([None] * leaf.ndim))
        return P_(tp_axis, *([None] * (leaf.ndim - 1)))   # expert-stacked

    param_specs = jax.tree_util.tree_map_with_path(pspec, p)

    def island(p_local, x_local):
        e_start = jax.lax.axis_index(tp_axis) * e_count
        y, aux = moe_apply(p_local, x_local, cfg, mlp_kind,
                           e_start=e_start, e_count=e_count,
                           psum_axis=tp_axis, slice_params=lambda a: a,
                           dropless=dropless)
        aux = jax.lax.pmean(jax.lax.pmean(aux, dp_axes), tp_axis)
        return y, aux

    fn = jax.shard_map(
        island, mesh=mesh,
        in_specs=(param_specs, P_(dp_axes, *([None] * (x.ndim - 1)))),
        out_specs=(P_(dp_axes, *([None] * (x.ndim - 1))), P_()),
        check_vma=False)
    return fn(p, x)


def moe_ref(p, x, cfg: MoEConfig, mlp_kind: str):
    """Reference: loop over experts, no capacity dropping.  Tests only."""
    dtype = x.dtype
    lead, D = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, D)
    logits = xf.astype(jnp.float32) @ p["router"]["w"]
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.normalize_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    y = jnp.zeros_like(xf)
    for e in range(cfg.n_experts):
        pe = {"up": p["up"][e:e+1], "down": p["down"][e:e+1]}
        if "gate" in p:
            pe["gate"] = p["gate"][e:e+1]
        he = _expert_ffn(pe, xf[None], mlp_kind, dtype)[0]
        w = jnp.where(top_e == e, top_p, 0).sum(-1).astype(dtype)
        y = y + he * w[:, None]
    if "shared" in p:
        from repro.models.layers import mlp as dense_mlp
        y = y + dense_mlp(p["shared"], xf, mlp_kind, dtype)
    return y.reshape(*lead, D)
