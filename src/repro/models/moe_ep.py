"""Expert-parallel MoE layer via shard_map — the production dispatch path.

Mapping of Janus's disaggregated data plane onto the SPMD mesh (DESIGN.md §2):

* activations enter **replicated over the model axis** — the SPMD image of
  EGate ("send complete activations to the MoE side and gate there"): no
  routing metadata or per-expert packing crosses the wire, and on a
  hierarchical mesh XLA decomposes the implied broadcast into the intra-pod →
  cross-pod two-phase pattern;
* each model-axis shard is one **MoE instance**: it redundantly runs gating
  and the (deterministic) scheduler on the same inputs — Janus's
  synchronisation-free trick — then computes only the expert slots it hosts;
* the combine is a ``psum`` over the model axis (intra-node all-reduce before
  cross-node transfer in the reverse direction, §3.3).

Two modes:
  * ``logical``   — buckets are logical experts block-partitioned over the
    model axis (training / monolithic-baseline semantics);
  * ``scheduled`` — buckets are physical replica slots; per-token routing is
    rewritten by the scheduler (AEBS or a baseline) before dispatch — the
    Janus serving path.

Two per-shard dispatch bodies:
  * ``dispatch="scatter"`` — legacy scatter/one-hot capacity dispatch.  In
    scheduled mode without pinned replica weights this materialises a full
    ``[S_total, d, f]`` weight copy every call (``gather_slot_weights``).
  * ``dispatch="grouped"`` — sort-based grouped dispatch
    (:func:`repro.models.moe.grouped_dispatch_ffn`).  Replica weights are
    *never* copied per step: pinned deployments index their local
    slot-stacked slabs with the identity map, and unpinned deployments read
    the logical ``[E, d, f]`` weights slot-indirectly through the shard's
    slice of ``slot_to_expert`` (a shard_map operand partitioned over the
    model axis).  Inactive slots stream no weights, so per-instance cost
    tracks the activated-expert count (β·a_max).

    Note the memory trade of the *unpinned* grouped route: the logical
    weights are replicated across the model axis (``P(None, ...)``), so each
    shard holds all E experts instead of an ``S_total/n_model`` slice.  For
    deployments where expert weights only fit partitioned, pin replicas at
    reconfiguration time (``launch.steps.materialize_slot_params`` — the
    faithful Janus layout, and what ``launch.steps.make_moe_ctx`` sets up);
    pinned + grouped keeps both the partitioned memory footprint and the
    copy-free hot path.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.ffn import ffn
from repro.models.moe import (
    gather_slot_weights,
    grouped_dispatch_ffn,
    load_balance_loss,
    route,
    scatter_dispatch_ffn,
)


def _pad_experts(w: jax.Array, e_pad: int) -> jax.Array:
    if w.shape[0] == e_pad:
        return w
    pad = e_pad - w.shape[0]
    return jnp.pad(w, ((0, pad),) + ((0, 0),) * (w.ndim - 1))


def moe_layer_ep(
    params: Dict[str, jax.Array],
    x: jax.Array,  # [b, s, d]
    cfg,
    *,
    mesh,
    dp_axes,
    model_axis: str,
    mode: str = "logical",  # logical | scheduled
    dispatch: str = "scatter",  # scatter | grouped (per-shard dispatch body)
    fsdp: bool = False,  # shard expert d_model over the data axes (training)
    scheduler: Optional[Callable] = None,
    layout_tables: Optional[Dict[str, jax.Array]] = None,
    slot_to_expert: Optional[jax.Array] = None,  # flat [S_total]
    num_instances: int = 0,
    capacity_factor: float = 2.0,
    with_aux: bool = False,
):
    b, s, d = x.shape
    n_model = mesh.shape[model_axis]
    dp_axes = tuple(a for a in dp_axes if a in mesh.shape)
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    batch_sharded = (b % n_dp) == 0 and n_dp > 1
    E, top_k = cfg.num_experts, cfg.top_k
    grouped = dispatch == "grouped"

    slot_indirect = False  # grouped + unpinned: logical weights + s2e slices
    if mode == "scheduled":
        assert slot_to_expert is not None and scheduler is not None
        total_slots = int(slot_to_expert.shape[0])
        assert total_slots % n_model == 0, (total_slots, n_model)
        if params["w_gate"].shape[0] == total_slots:
            # replica weights were pinned at deployment time
            # (launch.steps.materialize_slot_params) — the faithful Janus
            # layout: placement happens at reconfiguration, not per step.
            weights = {k: params[k] for k in ("w_gate", "w_up", "w_down")}
        elif grouped:
            # no per-step gather: each shard reads the logical weights
            # through its slice of slot_to_expert inside the dispatch body
            slot_indirect = True
            weights = {k: params[k] for k in ("w_gate", "w_up", "w_down")}
        else:
            weights = gather_slot_weights(params, slot_to_expert)
        buckets = total_slots
    else:
        e_pad = ((E + n_model - 1) // n_model) * n_model
        weights = {
            k: _pad_experts(params[k], e_pad) for k in ("w_gate", "w_up", "w_down")
        }
        buckets = e_pad

    buckets_local = buckets // n_model
    t_loc = (b // n_dp if batch_sharded else b) * s
    capacity = max(4, int(t_loc * top_k * capacity_factor / buckets))

    router_w = params["router"]
    n_sched = 3 if mode == "scheduled" else 0

    def body(xl, router_w, wg, wu, wd, *rest):
        # xl: [b_loc, s, d] — replicated over the model axis (EGate)
        g_idx = jax.lax.axis_index(model_axis)
        bl = xl.shape[0]
        x2d = xl.reshape(bl * s, d)
        if wg.shape[1] < d:
            # FSDP: weights arrive d_model-sharded over the data axes;
            # gather per layer (transpose = reduce-scatter of expert grads)
            wg = jax.lax.all_gather(wg, dp_axes, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, dp_axes, axis=1, tiled=True)
        if wd.shape[2] < d:
            wd = jax.lax.all_gather(wd, dp_axes, axis=2, tiled=True)
        gates, eids, probs = route(router_w, x2d, top_k)

        if mode == "scheduled":
            tables = {
                "expert_hosts": rest[0],
                "replica_counts": rest[1],
                "slot_of": rest[2],
            }
            bucket_ids, load, _ = scheduler(eids, tables, num_instances)
        else:
            bucket_ids = eids
            load = None

        owner = bucket_ids // buckets_local
        local_slot = bucket_ids % buckets_local
        is_local = (owner == g_idx).reshape(-1)
        w_local = {"w_gate": wg, "w_up": wu, "w_down": wd}
        if grouped:
            if slot_indirect:
                s2e_local = rest[n_sched]  # [buckets_local] this shard's slice
            elif mode == "scheduled":
                # pinned slot-stacked weights: identity map (still gets the
                # inactive-slot skip from the stream/kernel backends)
                s2e_local = jnp.arange(buckets_local, dtype=jnp.int32)
            else:
                s2e_local = None  # buckets are (padded) logical experts
            y = grouped_dispatch_ffn(
                x2d,
                local_slot,
                gates.astype(x2d.dtype),
                buckets_local,
                capacity,
                w_local,
                slot_to_expert=s2e_local,
                item_mask=is_local,
            )
        else:
            y = scatter_dispatch_ffn(
                x2d,
                local_slot,
                gates.astype(x2d.dtype),
                buckets_local,
                capacity,
                w_local,
                item_mask=is_local,
            )
        y = jax.lax.psum(y, model_axis)
        aux_out = {}
        if with_aux:
            lb = load_balance_loss(probs, eids, E)
            if batch_sharded:
                lb = jax.lax.pmean(lb, dp_axes)
            aux_out["lb_loss"] = lb
            if load is not None:
                # straggler semantics: the layer finishes with the slowest
                # (data-shard, instance) pair → report max over data shards
                aux_out["load"] = (
                    jax.lax.pmax(load, dp_axes) if batch_sharded else load
                )
        return y.reshape(bl, s, d), aux_out

    xspec = P(dp_axes if batch_sharded else None, None, None)
    d_ok = fsdp and dp_axes and d % n_dp == 0
    if slot_indirect:
        # logical weights replicated across the model axis; indirection
        # replaces the per-shard weight partition
        wspec_gu = P(None, dp_axes if d_ok else None, None)
        wspec_d = P(None, None, dp_axes if d_ok else None)
    else:
        wspec_gu = P(model_axis, dp_axes if d_ok else None, None)
        wspec_d = P(model_axis, None, dp_axes if d_ok else None)
    in_specs = [xspec, P(None, None), wspec_gu, wspec_gu, wspec_d]
    operands = []
    if mode == "scheduled":
        operands += [
            layout_tables["expert_hosts"],
            layout_tables["replica_counts"],
            layout_tables["slot_of"],
        ]
        in_specs += [P(None, None), P(None), P(None, None)]
    if slot_indirect:
        operands.append(jnp.asarray(slot_to_expert, jnp.int32))
        in_specs.append(P(model_axis))  # each shard sees its own slice

    aux_specs = {}
    if with_aux:
        aux_specs["lb_loss"] = P()
        if mode == "scheduled":
            aux_specs["load"] = P(None)

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(xspec, aux_specs),
        check_vma=False,
    )(x, router_w, weights["w_gate"], weights["w_up"], weights["w_down"], *operands)

    if "shared" in params:
        # shared expert stays on the "attention side" (data-parallel partition)
        # and overlaps with the dispatch/combine collectives (§4).
        y = y + ffn(params["shared"], x, "swiglu")
    if with_aux:
        return y, aux
    return y
