"""Jit-able FL round step — the pod-scale realization of the paper's FL loop.

One ``round_step`` = every sampled client runs (up to) ``max_steps`` local
SGD steps from the current global model, then the Strategy aggregates.  All
execution modes share ONE uniform contract::

    round_step(global_params, server_state, client_state, batches, weights,
               step_budgets, rnd, mask=None)
        -> (new_global, new_server_state, new_client_state, metrics)

``mask`` is the scheduler's **participation mask** — a static-shaped (C,)
0/1 float vector realizing a virtual-clock decision (core/scheduler.py:
deadline drops, availability dropouts) inside ONE jitted round: a masked
client still runs its shape-static local work, but contributes zero weight
under the existing ``safe_weight_sum`` denominator (so the aggregate is
bitwise what it would be without the client), its error-feedback residual
row carries UNCHANGED (it never transmitted, so no compression error
telescopes), and it is excluded from the loss/steps metrics.  ``mask=None``
(the default) takes the exact pre-mask code path — an all-ones mask and
``None`` produce bitwise-identical results on every mode.

``client_state`` is a codec-owned pytree
(``spec.codec.init_client_state(n_clients, n_params)``): error-feedback
codecs carry a fp32 residual buffer — one (C, n_params) block for a flat
codec, or a per-segment tuple of (C, seg.size) blocks when the codec
carries a ``SegmentMap`` (stateless segments hold ``()``) — so the
compression error telescopes across rounds; ``NullCodec`` — the default —
carries empty state, so the uncompressed engine allocates no client state
at all.  The engine never inspects the structure: it threads whatever the
codec initialized through ``aggregate_updates`` / ``transmit_tree``, so
flat and segmented codecs share every code path below.  The same
signature holds whether or not anything is compressed: there is no forked
"compressed round step" anymore.

Population mode (core/population.py) changes none of this: the engine
still receives dense, static-shaped ``client_state`` arrays (one
``(C, n_params)`` block, or the per-segment tuple) — the population layer
*gathers* the sampled cohort's resident rows into those arrays before the
call (row i belongs to cohort id i, missing/evicted rows are zeros) and
*scatters* ``new_client_state`` back by the same id order afterwards.  C is the fixed cohort size, never the population size,
so the jitted program, the participation mask, and the codec contracts are
unchanged shape-wise round to round.

Three mesh mappings (DESIGN.md §4), every one codec-aware:

- **parallel** (no mesh): params/batches carry a leading client axis C;
  local training is vmapped over clients; per-client flat deltas (plus the
  carried residual) are encoded and the server aggregates straight off the
  encoded payload (``codec.aggregate_batch`` — for Int8 the fused
  dequantize+weighted-reduce Pallas kernel: one HBM pass over the int8
  payload; for TopK the scatter-accumulate kernel over the (idx, val)
  payloads: O(C·k), the dense (C, n_params) delta matrix is never built).
- **parallel + mesh**: clients map 1:1 onto ``client_axes`` via shard_map
  (manual over client axes, auto over model axes).  Each client's delta is
  encoded *before* the hierarchical cross-client/cross-pod psum — the slow
  inter-pod links are exactly where wire shrinkage pays — so the values
  crossing the links carry only codec-representable information
  (``codec.transmit_tree``: encode -> decode inside the manual region; the
  psum operand is the decoded payload, numerically identical to the server
  decoding every client's uplink).

  **Collective wire contract** (``RoundSpec.collective``): the psum operand
  is always a *partial weighted sum* — ``decoded_delta * w_c`` — which is
  the one form that commutes with the reduction (sum of weighted terms,
  divided once by the psum'd ``safe_weight_sum`` denominator; the same
  contract the strategy-side wire reduce uses group-wise).  ``"fp32"``
  (default) psums that operand as-is, bitwise the pre-compression path.
  ``"int8"`` (``CompressedPsum``) quantizes it per 256-elem block against
  a scale *shared by every reducing device* — each device computes its
  local block-absmax, a cheap ``lax.pmax`` sidecar (4 B/block + the fp32
  weight denominator) agrees on the max BEFORE anything quantizes, and
  then every device's payload lives on one scale grid, so the int32 psum
  accumulates exactly (``unpack(sum_d pack(x_d))`` matches
  ``sum_d unpack(pack(x_d))`` to one final fp32 rounding — no per-hop
  requantization error).  Payload values are clipped to [-127, 127]
  (one byte on the wire; the int32 container is the *accumulator* dtype,
  not the wire format) so the summed accumulator provably cannot overflow
  below a fan-in of 2^31/127 ≈ 16.9M devices — no per-hop requantization,
  ONE fused dequant after the last hop.  The per-device quantization error
  lands in a collective error-feedback residual (``client_state =
  (codec_state, resid)``, rows sharded P(client_axes)) that telescopes
  across rounds exactly like the uplink codecs'.  A masked device
  transmits nothing — not even its carried residual — and keeps its
  residual row unchanged.  This shared-scale/partial-sum layout is also
  the substrate a secure-aggregation codec needs: masked integer payloads
  on a common grid sum server-side without per-client decode.
- **sequential**: one client at a time occupies the whole mesh (scan over
  clients); each client's delta goes through the codec round-trip before
  entering the accumulated weighted delta, and the per-client state rows
  are scanned alongside.  ``NullCodec``'s identity ``transmit_tree`` keeps
  the bf16 dense accumulator and never flattens a sharded model.  Caveat:
  an error-feedback codec here still materializes a replicated flat delta
  per scan step; a segmented codec at least splits its fp32 state into
  per-segment (C, seg.size) blocks (so no single (C, n_params) monolith),
  but the blocks remain unsharded by default — fine for models whose flat
  update fits on one host; for multi-B fsdp archs lay the per-segment
  (C, seg.size) blocks out along the mesh with
  ``models.sharding.shard_client_state`` (parameter dim over the fsdp
  axes, client dim whole — placement only, values bitwise unchanged), so
  per-device state memory drops by the full fsdp factor.

A heterogeneous fleet runs inside ONE jitted round via ``MixedCodec``: its
static per-client assignment partitions the client axis into per-codec
groups at trace time — the parallel path aggregates group-wise through
``codec.aggregate_updates`` (each group on its own kernel path, partial
weighted sums combined under one fleet denominator), the sequential path
runs one scan per group (each scan body closes over its group's wire
format) with the carried delta accumulator threading across scans.
``client_state`` is then a per-group tuple.  The mesh shard_map path
rejects ``MixedCodec`` at build time: one SPMD program, one wire format.

The paper's tau-cutoff becomes a *per-client step budget* ``step_budgets``
(int (C,)): clients keep stepping while ``i < budget_c`` and freeze their
parameters afterwards — shape-static, mask-realized partial work.

Rounds-as-scan (``make_multi_round_step``)
------------------------------------------

The uniform ``round_step`` is also the body of ONE ``lax.scan`` over R
rounds, so a whole training run compiles to a single traced program
(``Server.run_scanned`` is the driver; ``benchmarks/scan_bench.py``
measures the rounds/sec win over the per-round python loop).

- **Carry**: ``(global_params, server_state, client_state)`` — exactly
  the three state pytrees every ``round_step`` threads.  The driver jits
  with ``donate_argnums=(0, 1, 2)`` so XLA aliases the carry buffers
  in place and peak memory stays flat in R.
- **xs**: ``rnd`` (int32 (R,)), per-round batch slices when batches are
  stacked (R, C, ...) (per-round-constant (C, ...) batches are instead
  closed over, keeping memory flat in R), and the precomputed (R, C)
  schedule rows — availability (``AvailabilityTrace.available_matrix``),
  finish-time offsets (``CostModel.fleet_time_matrix``), and cohort
  priorities (``cohort_priority_matrix``).  All churn/jitter randomness
  is decided host-side before the trace, from the same seeded draws the
  event-driven driver makes.
- **Body**: dispatch mask = availability ∩ on-device cohort top-k
  (``cohort_dispatch_mask``), then the static policy's pure-array
  verdict (``RoundPolicy.plan_arrays``) picks the reporters and the
  round's wall clock, and ``round_step`` runs under that mask.
- **ys**: the per-round metrics dict plus masks/wall/participation
  counts, stacked on device and decoded to a ``History`` once at the
  end — no host sync inside the run.

Which policies can trace: ``SyncAll`` and ``Deadline`` — their verdict
is a pure function of THIS round's dispatch set and finish times.
``BufferedAsync`` cannot (v1): its pending set is data-dependent-size
state threaded between rounds (an arrival consumed at round r may have
launched at r-3), which has no static-shape scan carry without a
fixed-slot in-flight buffer — future work, documented out of scope.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models.sharding import shard_map_compat as _shard_map
from repro.optim import Optimizer
from repro.utils.pytree import safe_weight_sum, tree_where

from .compression import CompressedPsum, MixedCodec, NullCodec
from .strategy.base import Strategy

PyTree = Any


@dataclass(frozen=True)
class RoundSpec:
    """Static configuration of the jitted round step."""

    max_steps: int               # scanned local steps (tau masks within)
    execution_mode: str          # "parallel" | "sequential" | "fsdp"
    prox_mu: float = 0.0         # FedProx proximal coefficient (0 = off)
    microbatches: int = 1        # gradient accumulation within one local step
    codec: Any = field(default_factory=NullCodec)  # UpdateCodec (wire format)
    # mesh-path collective wire: "fp32" (default — bitwise the pre-existing
    # psum) or "int8" (CompressedPsum; opt-in, tolerance-bounded parity)
    collective: str = "fp32"
    collective_block: int = 256  # scale-block size of the int8 collective


def make_client_update(
    loss_fn: Callable,           # (params, batch) -> (loss, metrics)
    opt: Optimizer,
    spec: RoundSpec,
    trainable_mask: PyTree | None = None,
):
    """Returns client_update(global_params, batches, step_budget) ->
    (new_params, mean_loss, steps_done) for ONE client.

    batches: pytree with leading (max_steps, ...) axis.
    """

    def total_loss(params, batch, global_params):
        with jax.named_scope("fl.local.loss"):
            loss, metrics = loss_fn(params, batch)
            if spec.prox_mu > 0.0:
                from repro.utils.pytree import tree_sq_norm, tree_sub

                loss = loss + 0.5 * spec.prox_mu * tree_sq_norm(
                    tree_sub(params, global_params)
                )
        return loss, metrics

    def client_update(global_params, batches, step_budget):
        opt_state = opt.init(global_params)

        def grad_of(params, batch):
            if spec.microbatches <= 1:
                (loss, _), grads = jax.value_and_grad(total_loss, has_aux=True)(
                    params, batch, global_params
                )
                return loss, grads

            # gradient accumulation: scan over microbatch slices of the batch
            # dim (activation memory / microbatches; bf16 accumulators)
            mb = spec.microbatches

            def split(x):
                b = x.shape[0]
                return x.reshape(mb, b // mb, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc_step(carry, mbatch):
                loss_acc, gacc = carry
                (loss, _), grads = jax.value_and_grad(total_loss, has_aux=True)(
                    params, mbatch, global_params
                )
                gacc = jax.tree.map(lambda a, g: a + g.astype(a.dtype), gacc, grads)
                return (loss_acc + loss, gacc), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)
            (loss_sum, gacc), _ = jax.lax.scan(
                acc_step, (jnp.zeros(()), zeros), micro
            )
            grads = jax.tree.map(lambda g: (g / mb).astype(jnp.bfloat16), gacc)
            return loss_sum / mb, grads

        def one_step(carry, xs):
            params, opt_state, i = carry
            batch = xs
            loss, grads = grad_of(params, batch)
            with jax.named_scope("fl.local.update"):
                new_params, new_opt_state = opt.update(grads, params, opt_state, i)
                if trainable_mask is not None:
                    new_params = jax.tree.map(
                        lambda n, o, m: n if m else o, new_params, params,
                        trainable_mask,
                    )
                live = i < step_budget
                params = tree_where(live, new_params, params)
                opt_state = tree_where(live, new_opt_state, opt_state)
                loss = jnp.where(live, loss, 0.0)
            return (params, opt_state, i + 1), loss

        (params, _, _), losses = jax.lax.scan(
            one_step, (global_params, opt_state, jnp.zeros((), jnp.int32)), batches,
            length=spec.max_steps,
        )
        steps_done = jnp.minimum(step_budget, spec.max_steps)
        mean_loss = jnp.sum(losses) / jnp.maximum(1, steps_done)
        return params, mean_loss, steps_done

    return client_update


def init_collective_residual(global_params: PyTree, n_clients: int) -> PyTree:
    """Zero per-device error-feedback state for the int8 collective
    (``RoundSpec(collective="int8")``): one fp32 buffer per model leaf with
    a leading client axis — on the mesh path clients map 1:1 onto devices,
    so row i is device i's residual and shards P(client_axes) like every
    other client-state block.  The mesh ``round_step`` then expects
    ``client_state = (codec_state, this)``."""
    return jax.tree.map(
        lambda g: jnp.zeros((n_clients,) + g.shape, jnp.float32),
        global_params,
    )


def _state_metrics(new_client_state) -> dict:
    """Residual-norm telemetry when the codec carries per-client state.

    Handles the per-group tuple state of ``MixedCodec`` too: every leaf is a
    (C_g, n_params) residual block; the mean is over ALL residual rows of
    the fleet (groups without state — Null — simply contribute no rows)."""
    rows = [
        jnp.linalg.norm(leaf.reshape(leaf.shape[0], -1), axis=-1)
        for leaf in jax.tree.leaves(new_client_state)
        if leaf.ndim >= 2 and leaf.shape[0] > 0
    ]
    if not rows:
        return {}
    return {"residual_norm_mean": jnp.mean(jnp.concatenate(rows))}


def _carry_masked_state(codec, mask, old_state, new_state):
    """Masked (non-participating) clients' codec state rows carry unchanged.

    A dropped client never transmitted, so its error-feedback residual must
    not absorb this round's untransmitted delta — the row it entered the
    round with is the row it leaves with.  Handles ``MixedCodec``'s
    per-group tuple state by slicing the fleet mask with each group's
    static client indices.
    """
    def keep_rows(m):
        mc = jnp.asarray(m)

        def leaf(o, n):
            return jnp.where(
                mc.reshape((-1,) + (1,) * (n.ndim - 1)) > 0, n, o
            )

        return leaf

    if isinstance(codec, MixedCodec):
        out = list(new_state)
        for g in range(len(codec.codecs)):
            if not jax.tree.leaves(new_state[g]):
                continue  # stateless group (Null): nothing to carry
            # static python index list (the assignment is a trace-time
            # constant) — no host numpy inside the traced region
            idx = [i for i, a in enumerate(codec.assignment) if a == g]
            out[g] = jax.tree.map(
                keep_rows(mask[jnp.asarray(idx)]), old_state[g], new_state[g]
            )
        return tuple(out)
    if not jax.tree.leaves(new_state):
        return new_state
    return jax.tree.map(keep_rows(mask), old_state, new_state)


def _masked_metrics(losses, steps, weights, mask):
    """Participation-aware loss/steps metrics (one definition, all modes).

    ``jnp.where`` — not multiplication — so a masked client's loss can be
    NaN/inf (it diverged, which may be WHY it was dropped) without
    poisoning the fleet metrics.
    """
    wf = weights.astype(jnp.float32)
    if mask is None:
        return {
            "client_loss_mean": jnp.sum(losses * wf) / safe_weight_sum(wf),
            "client_loss_max": jnp.max(losses),
            "steps_total": jnp.sum(steps),
        }
    mf = mask.astype(jnp.float32)
    w_eff = wf * mf
    losses_eff = jnp.where(mf > 0, losses, 0.0)
    # a fully-masked round has no defined loss: NaN (matching the Server's
    # empty-round train_loss), never a 0.0 that reads like convergence or a
    # -inf max that poisons series mins downstream
    any_live = jnp.any(mf > 0)
    return {
        "client_loss_mean": jnp.where(
            any_live,
            jnp.sum(losses_eff * w_eff) / safe_weight_sum(w_eff), jnp.nan,
        ),
        "client_loss_max": jnp.where(
            any_live, jnp.max(jnp.where(mf > 0, losses, -jnp.inf)), jnp.nan
        ),
        "steps_total": jnp.sum(jnp.where(mf > 0, steps, 0)),
    }


def make_round_step(
    loss_fn: Callable,
    opt: Optimizer,
    strategy: Strategy,
    spec: RoundSpec,
    trainable_mask: PyTree | None = None,
    mesh=None,
    client_axes: tuple[str, ...] = ("data",),
    param_shardings: PyTree | None = None,
):
    """Builds the uniform round_step (module docstring) for ``spec``.

    parallel:   batches leaves (C, max_steps, B, ...); weights/budgets (C,);
                client_state leaves lead with C.  With a mesh, clients map
                1:1 onto `client_axes` via shard_map; without one (CPU
                tests) local training vmaps over clients.
    sequential: identical signature; clients are scanned, not mapped.

    Aggregation is codec-mediated on every path: the weighted mean of the
    codec-decoded deltas feeds ``strategy.server_update`` (FedAvg-family:
    identity; FedOpt: server optimizer on the pseudo-gradient).
    """
    client_update = make_client_update(loss_fn, opt, spec, trainable_mask)
    codec = spec.codec if spec.codec is not None else NullCodec()

    if spec.collective not in ("fp32", "int8"):
        raise ValueError(
            f"RoundSpec.collective={spec.collective!r}: expected fp32 | int8"
        )
    compressed_collective = spec.collective == "int8"
    if compressed_collective and (
        mesh is None or spec.execution_mode != "parallel"
    ):
        raise NotImplementedError(
            "collective='int8' compresses the mesh shard_map psum — it "
            "requires execution_mode='parallel' with a mesh; the vmap and "
            "sequential modes have no cross-device collective to compress"
        )

    if spec.execution_mode == "parallel" and mesh is not None:
        if isinstance(codec, MixedCodec):
            raise NotImplementedError(
                "MixedCodec is not supported on the mesh shard_map path: an "
                "SPMD program runs ONE wire format per device; use the "
                "vmap-parallel or sequential execution mode for mixed fleets"
            )
        from jax.sharding import PartitionSpec as P

        axes = client_axes
        cpsum = (
            CompressedPsum(block=spec.collective_block)
            if compressed_collective else None
        )

        def per_client(global_params, batches, weight, budget, mask_c, state):
            if compressed_collective:
                codec_state, coll_resid = state
            else:
                codec_state, coll_resid = state, None
            b0 = jax.tree.map(lambda x: x[0], batches)
            new_p, loss, steps = client_update(global_params, b0, budget[0])

            # this client's uplink: encode the delta BEFORE anything crosses
            # the mesh — only codec-representable values enter the psum
            delta = jax.tree.map(
                lambda n, g: n.astype(jnp.float32) - g.astype(jnp.float32),
                new_p, global_params,
            )
            state_row = jax.tree.map(lambda x: x[0], codec_state)
            with jax.named_scope("fl.encode"):
                dec_delta, new_row = codec.transmit_tree(delta, state_row)
            if mask_c is not None:
                # participation mask: a dropped client never transmitted —
                # its residual row carries unchanged across the round, and
                # its delta is zeroed BEFORE the psum (zero weight alone
                # would let a diverged client's 0 * NaN poison the sum)
                new_row = jax.tree.map(
                    lambda n, o: jnp.where(mask_c[0] > 0, n, o),
                    new_row, state_row,
                )
                dec_delta = jax.tree.map(
                    lambda d: jnp.where(mask_c[0] > 0, d, jnp.zeros_like(d)),
                    dec_delta,
                )

            wf = weight[0].astype(jnp.float32)
            if mask_c is not None:
                wf = wf * mask_c[0].astype(jnp.float32)
            wsum = wf
            for ax in reversed(axes):
                wsum = jax.lax.psum(wsum, ax)
            wsum = jnp.where(wsum == 0.0, 1.0, wsum)  # safe_weight_sum, post-psum

            if not compressed_collective:
                def wmean(d):
                    wx = d.astype(jnp.float32) * wf
                    # hierarchical aggregation: reduce inside the pod first,
                    # then across pods (one pre-reduced tensor crosses the
                    # slow links)
                    for ax in reversed(axes):
                        wx = jax.lax.psum(wx, ax)
                    return wx / wsum

                with jax.named_scope("fl.reduce"):
                    avg = jax.tree.map(
                        lambda g, d: (g.astype(jnp.float32) + wmean(d)).astype(g.dtype),
                        global_params, dec_delta,
                    )
                return avg, loss[None], steps[None], jax.tree.map(
                    lambda x: x[None], new_row
                )

            # int8 collective (module docstring: the collective wire
            # contract): quantize this device's partial weighted sum per
            # leaf against a pmax-shared block scale, psum the int payload
            # hierarchically, dequant ONCE after the last hop.  The
            # per-device quantization residual stays local and telescopes.
            resid_row = jax.tree.map(lambda x: x[0], coll_resid)
            live = None if mask_c is None else mask_c[0] > 0

            def leaf_psum(d, r):
                wx = d.astype(jnp.float32).reshape(-1) * wf
                r = r.reshape(-1)
                if live is not None:
                    # a dropped device transmits NOTHING — not even its
                    # carried residual — and keeps the residual unchanged
                    r_in = jnp.where(live, r, 0.0)
                else:
                    r_in = r
                total, new_r = cpsum.psum(wx, r_in, axes)
                if live is not None:
                    new_r = jnp.where(live, new_r, r)
                return total.reshape(d.shape), new_r.reshape(d.shape)

            leaves_d, treedef = jax.tree_util.tree_flatten(dec_delta)
            leaves_r = treedef.flatten_up_to(resid_row)
            with jax.named_scope("fl.reduce"):
                pairs = [leaf_psum(d, r) for d, r in zip(leaves_d, leaves_r)]
                sums = jax.tree_util.tree_unflatten(treedef, [p[0] for p in pairs])
                new_resid_row = jax.tree_util.tree_unflatten(
                    treedef, [p[1] for p in pairs]
                )
                avg = jax.tree.map(
                    lambda g, s: (g.astype(jnp.float32) + s / wsum).astype(g.dtype),
                    global_params, sums,
                )
            return avg, loss[None], steps[None], (
                jax.tree.map(lambda x: x[None], new_row),
                jax.tree.map(lambda x: x[None], new_resid_row),
            )

        def round_step(
            global_params, server_state, client_state, batches, weights,
            step_budgets, rnd, mask=None,
        ):
            batch_specs = jax.tree.map(lambda x: P(axes), batches)
            param_specs_manual = jax.tree.map(lambda x: P(), global_params)
            state_specs = jax.tree.map(
                lambda x: P(axes, *([None] * (x.ndim - 1))), client_state
            )
            if mask is None:
                body = lambda gp, b, w, bu, st: per_client(gp, b, w, bu, None, st)
                in_specs = (
                    param_specs_manual, batch_specs, P(axes), P(axes), state_specs,
                )
                args = (global_params, batches, weights, step_budgets, client_state)
            else:
                body = per_client
                in_specs = (
                    param_specs_manual, batch_specs, P(axes), P(axes), P(axes),
                    state_specs,
                )
                args = (
                    global_params, batches, weights, step_budgets, mask,
                    client_state,
                )
            avg, losses, steps, new_client_state = _shard_map(
                body,
                mesh,
                in_specs=in_specs,
                out_specs=(param_specs_manual, P(axes), P(axes), state_specs),
                axis_names=set(axes),
            )(*args)
            with jax.named_scope("fl.server_update"):
                new_global, new_state = strategy.server_update(
                    avg, global_params, server_state, rnd
                )
            metrics = {
                # examples-weighted, like every other execution mode: the
                # same round must report the same metric everywhere
                **_masked_metrics(losses, steps, weights, mask),
            }
            if compressed_collective:
                # keep the uplink codec's residual telemetry separate from
                # the collective's own error-feedback buffer
                metrics.update(_state_metrics(new_client_state[0]))
                coll = _state_metrics(
                    tuple(
                        leaf.reshape(leaf.shape[0], -1)
                        for leaf in jax.tree.leaves(new_client_state[1])
                    )
                )
                if coll:
                    metrics["collective_residual_norm_mean"] = coll[
                        "residual_norm_mean"
                    ]
            else:
                metrics.update(_state_metrics(new_client_state))
            return new_global, new_state, new_client_state, metrics

        return round_step

    if spec.execution_mode == "parallel":

        def round_step(
            global_params, server_state, client_state, batches, weights,
            step_budgets, rnd, mask=None,
        ):
            new_params, losses, steps = jax.vmap(
                client_update, in_axes=(None, 0, 0)
            )(global_params, batches, step_budgets)

            # codec-owned aggregation: wire layout + encoded-payload reduce
            # for compressing codecs, a leafwise weighted mean for NullCodec.
            # A masked client aggregates at zero weight (zero contribution
            # under the one safe_weight_sum denominator); its params are
            # pinned back to the global FIRST — zero weight alone is not
            # enough, a diverged (NaN/inf) dropped client would still
            # poison the reduce through 0 * NaN...
            if mask is not None:
                new_params = jax.tree.map(
                    lambda p, g: jnp.where(
                        mask.reshape((-1,) + (1,) * g.ndim) > 0, p, g[None]
                    ),
                    new_params, global_params,
                )
            w_agg = weights if mask is None else (
                weights.astype(jnp.float32) * mask.astype(jnp.float32)
            )
            # the codec's encode side runs under its own fl.encode scope
            with jax.named_scope("fl.reduce"):
                avg_params, new_client_state = codec.aggregate_updates(
                    new_params, global_params, w_agg, client_state
                )
            if mask is not None:
                # ...and, having transmitted nothing, keeps its residual row
                new_client_state = _carry_masked_state(
                    codec, mask, client_state, new_client_state
                )
            with jax.named_scope("fl.server_update"):
                new_global, new_state = strategy.server_update(
                    avg_params, global_params, server_state, rnd
                )
            metrics = {
                # examples-weighted (matches the sequential scan's running
                # weighted mean): one metric definition across all modes
                **_masked_metrics(losses, steps, weights, mask),
                **_state_metrics(new_client_state),
            }
            return new_global, new_state, new_client_state, metrics

        return round_step

    def _pin(tree):
        """Pin the fp32 delta accumulator to the parameter sharding —
        without this the scan carry (initialized from plain zeros) can end
        up replicated, which for a multi-B model is fatal."""
        if param_shardings is None:
            return tree
        return jax.lax.with_sharding_constraint(tree, param_shardings)

    def round_step(
        global_params, server_state, client_state, batches, weights,
        step_budgets, rnd, mask=None,
    ):
        wf = weights.astype(jnp.float32)
        mf = None if mask is None else mask.astype(jnp.float32)
        wsum = safe_weight_sum(wf if mf is None else wf * mf)

        def make_per_client(codec_g):
            def per_client(carry, xs):
                delta_acc, loss_acc, loss_max, steps_acc = carry
                if mf is None:
                    client_batches, w, budget, state_row = xs
                    m = None
                else:
                    client_batches, w, budget, m, state_row = xs
                new_params, loss, steps = client_update(
                    global_params, client_batches, budget
                )
                delta = jax.tree.map(jnp.subtract, new_params, global_params)
                # codec round-trip: only what survives the wire is accumulated
                with jax.named_scope("fl.encode"):
                    dec_delta, new_row = codec_g.transmit_tree(delta, state_row)
                if m is not None:
                    # masked client: zero aggregation weight AND a zeroed
                    # delta (0 * NaN from a diverged dropped client would
                    # still poison the accumulator), residual row carried
                    # unchanged (it never transmitted), metrics skip
                    w = w * m
                    dec_delta = jax.tree.map(
                        lambda d: jnp.where(m > 0, d, jnp.zeros_like(d)),
                        dec_delta,
                    )
                    new_row = jax.tree.map(
                        lambda n, o: jnp.where(m > 0, n, o), new_row, state_row
                    )
                    loss = jnp.where(m > 0, loss, 0.0)
                    loss_for_max = jnp.where(m > 0, loss, -jnp.inf)
                    steps = jnp.where(m > 0, steps, 0)
                else:
                    loss_for_max = loss
                with jax.named_scope("fl.reduce"):
                    scale = (w / wsum).astype(jnp.bfloat16)
                    delta_acc = _pin(jax.tree.map(
                        lambda acc, d: acc + scale * d.astype(jnp.bfloat16),
                        delta_acc, dec_delta,
                    ))
                carry = (
                    delta_acc,
                    loss_acc + loss * w / wsum,
                    jnp.maximum(loss_max, loss_for_max),
                    steps_acc + steps,
                )
                return carry, new_row

            return per_client

        # bf16 delta accumulator: halves the largest param-state buffer; the
        # single-round accumulation error is far below local-SGD noise
        zero_delta = _pin(jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.bfloat16), global_params
        ))
        carry = (
            zero_delta, jnp.zeros(()), jnp.full((), -jnp.inf),
            jnp.zeros((), jnp.int32),
        )
        if isinstance(codec, MixedCodec):
            # one scan per codec group: the assignment is static, so each
            # group's rows are gathered at trace time and its wire format is
            # a trace-time constant inside its scan body; the carried delta
            # accumulator and loss/steps stats thread across the group
            # scans, all normalized by the ONE fleet-wide weight sum
            new_states = list(client_state)
            for g, codec_g, idx in codec.groups():
                ia = jnp.asarray(idx)  # static rows -> constant gather
                xs_g = (
                    jax.tree.map(lambda x: x[ia], batches),
                    wf[ia], step_budgets[ia],
                    *(() if mf is None else (mf[ia],)),
                    client_state[g],
                )
                carry, new_states[g] = jax.lax.scan(
                    make_per_client(codec_g), carry, xs_g
                )
            new_client_state = tuple(new_states)
        else:
            carry, new_client_state = jax.lax.scan(
                make_per_client(codec), carry,
                (batches, wf, step_budgets,
                 *(() if mf is None else (mf,)), client_state),
            )
        delta, loss_mean, loss_max, steps_total = carry
        if mf is not None:
            # fully-masked round: no defined loss (see _masked_metrics)
            any_live = jnp.any(mf > 0)
            loss_mean = jnp.where(any_live, loss_mean, jnp.nan)
            loss_max = jnp.where(any_live, loss_max, jnp.nan)
        # the averaged delta goes straight through server_update (FedAvg:
        # identity; FedOpt: server optimizer) — no stacked fp32 detour.
        with jax.named_scope("fl.reduce"):
            avg_params = _pin(jax.tree.map(
                lambda g, d: (g.astype(jnp.float32) + d.astype(jnp.float32)).astype(g.dtype),
                global_params, delta,
            ))
        with jax.named_scope("fl.server_update"):
            new_global, new_state = strategy.server_update(
                avg_params, global_params, server_state, rnd
            )
        metrics = {
            "client_loss_mean": loss_mean,
            "client_loss_max": loss_max,
            "steps_total": steps_total,
            **_state_metrics(new_client_state),
        }
        return new_global, new_state, new_client_state, metrics

    return round_step


def cohort_dispatch_mask(priorities, avail_mask, cohort_size: int):
    """On-device cohort sampling: the ``cohort_size`` available clients
    with the LOWEST priorities win (uniform priorities == a uniform draw
    without replacement).

    Pure array code so it runs identically traced inside the scan body and
    eagerly in the reference driver.  Unavailable clients rank at +inf, so
    a round with fewer than ``cohort_size`` available clients dispatches
    only whoever is up (including nobody) — the scan-world analogue of
    ``Strategy.sample_cohort``'s short-cohort contract.  The double stable
    argsort turns priorities into dense ranks; ties (exactly equal float
    priorities) break by client id, deterministically.
    """
    pri = jnp.where(avail_mask > 0, priorities, jnp.inf)
    order = jnp.argsort(pri, stable=True)
    ranks = jnp.argsort(order, stable=True)
    return jnp.where((ranks < cohort_size) & (avail_mask > 0), 1.0, 0.0)


def make_multi_round_step(
    loss_fn: Callable,
    opt: Optimizer,
    strategy: Strategy,
    spec: RoundSpec,
    num_rounds: int,
    *,
    policy=None,
    tau: float | None = None,
    cohort_size: int | None = None,
    trainable_mask: PyTree | None = None,
    mesh=None,
    client_axes: tuple[str, ...] = ("data",),
    param_shardings: PyTree | None = None,
    stacked_batches: bool = True,
):
    """Compile ``num_rounds`` FL rounds into ONE ``lax.scan`` over the
    uniform ``round_step`` (module docstring: "the scanned trainer").

    Returns::

        multi_round_step(global_params, server_state, client_state,
                         batches, weights, step_budgets,
                         avail, t_total, priorities)
            -> (new_global, new_server_state, new_client_state, stacked)

    where ``avail`` / ``t_total`` / ``priorities`` are the precomputed
    (R, C) schedule matrices (``AvailabilityTrace.available_matrix``,
    ``CostModel.fleet_time_matrix``, ``cohort_priority_matrix``) and
    ``stacked`` is a dict of (R,)- and (R, C)-shaped per-round outputs
    (the round_step metrics plus ``participation_mask``,
    ``dispatch_mask``, ``round_wall_s``, ``participants``,
    ``dispatched``) decoded to a ``History`` once, after the scan.

    ``batches``: leaves lead with (R, C, max_steps, ...) when
    ``stacked_batches`` (each round gets its own slice) or (C, max_steps,
    ...) when not — the same batch every round, closed over as a
    scan-invariant constant so device memory stays flat in R.

    Scheduling is the static ``policy``'s pure-array verdict
    (``RoundPolicy.plan_arrays``): each round the body computes a
    dispatch mask (availability ∩ on-device cohort top-k when
    ``cohort_size`` is set), asks the policy who reports and how long the
    round ran, and feeds the reporter mask to ``round_step`` — deadline
    drops, churn, and sampling all happen on device.  ``tau`` must be a
    pre-resolved host float (``Deadline.resolve_tau``); only
    ``traceable`` policies are accepted (``SyncAll``, ``Deadline`` —
    ``BufferedAsync`` carries a cross-round pending set and cannot trace,
    see ``core/scheduler.py``).
    """
    from .scheduler import SyncAll

    round_step = make_round_step(
        loss_fn, opt, strategy, spec, trainable_mask, mesh, client_axes,
        param_shardings,
    )
    policy = SyncAll() if policy is None else policy
    if not getattr(policy, "traceable", False):
        raise NotImplementedError(
            f"{type(policy).__name__} cannot run inside lax.scan: its "
            "verdict depends on cross-round pending-arrival state (see "
            "core/scheduler.py); use Server.run, or a traceable policy "
            "(SyncAll, Deadline)"
        )
    R = num_rounds  # build-time static (no cast: this fn is a lint root)

    def multi_round_step(
        global_params, server_state, client_state, batches, weights,
        step_budgets, avail, t_total, priorities,
    ):
        def body(carry, xs):
            g, ss, cs = carry
            if stacked_batches:
                rnd, batch_r, avail_r, t_r, pri_r = xs
            else:
                rnd, avail_r, t_r, pri_r = xs
                batch_r = batches
            if cohort_size is None:
                dispatch_mask = avail_r
            else:
                dispatch_mask = cohort_dispatch_mask(
                    pri_r, avail_r, cohort_size
                )
            mask, round_end = policy.plan_arrays(dispatch_mask, t_r, tau=tau)
            g, ss, cs, met = round_step(
                g, ss, cs, batch_r, weights, step_budgets, rnd, mask
            )
            ys = {
                **met,
                "participation_mask": mask,
                "dispatch_mask": dispatch_mask,
                "round_wall_s": round_end,
                "participants": jnp.sum(jnp.where(mask > 0, 1.0, 0.0)),
                "dispatched": jnp.sum(jnp.where(dispatch_mask > 0, 1.0, 0.0)),
            }
            return (g, ss, cs), ys

        rnds = jnp.arange(1, R + 1, dtype=jnp.int32)
        xs = (
            rnds,
            *((batches,) if stacked_batches else ()),
            avail, t_total, priorities,
        )
        (g, ss, cs), stacked = jax.lax.scan(
            body, (global_params, server_state, client_state), xs
        )
        return g, ss, cs, stacked

    return multi_round_step
