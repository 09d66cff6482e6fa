"""Training step builders.

``make_train_step``        — pjit path used by the dry-run grid: grads via
                             value_and_grad, optional microbatch accumulation
                             (lax.scan), optimizer update.  XLA SPMD inserts
                             the collectives implied by the shardings.
``make_dp_train_step``     — explicit shard_map data-parallel path where the
                             gradient collective is OURS to schedule.  The
                             paper's transfer schemes become collective
                             schedules:
                               per-tensor psum   = per-leaf deep copy (UVM-ish)
                               arena-fused psum  = marshalling (Alg. 1) on ICI
                             optionally int8+error-feedback compressed.
benchmarks/collective_fusion.py parses both HLOs and counts collective ops.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core import engine as engine_lib
from ..core.spec import TransferSpec
from ..models.registry import ModelApi
from ..optim.optimizers import Optimizer
from ..optim import compression


def train_state(api: ModelApi, optimizer: Optimizer, key) -> Dict[str, Any]:
    params = api.init(key)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_train_state(api: ModelApi, optimizer: Optimizer) -> Dict[str, Any]:
    params = api.abstract()
    return {"params": params, "opt": optimizer.abstract(params),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def train_state_axes(api: ModelApi, optimizer: Optimizer) -> Dict[str, Any]:
    axes = api.axes()
    return {"params": axes, "opt": optimizer.axes(axes), "step": ()}


def _split_micro(batch: Dict[str, jax.Array], m: int) -> Dict[str, jax.Array]:
    return jax.tree_util.tree_map(
        lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch)


def make_train_step(api: ModelApi, optimizer: Optimizer,
                    lr_schedule: Callable) -> Callable:
    cfg = api.cfg
    m = cfg.micro_batches

    def loss_for_grad(params, batch):
        loss, metrics = api.loss_fn(params, batch)
        return loss, metrics

    def train_step(state, batch):
        params = state["params"]
        if m > 1:
            micro = _split_micro(batch, m)

            def acc(carry, mb):
                gsum, lsum = carry
                (loss, metrics), g = jax.value_and_grad(
                    loss_for_grad, has_aux=True)(params, mb)
                gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                return (gsum, lsum + loss), metrics["tokens"]

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(
                acc, (zeros, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree_util.tree_map(lambda g: g / m, gsum)
            loss = lsum / m
            metrics = {"loss": loss}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_for_grad, has_aux=True)(params, batch)

        lr = lr_schedule(state["step"])
        new_params, new_opt = optimizer.update(grads, state["opt"], params, lr)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree_util.tree_leaves(grads)))
        out_metrics = {"loss": metrics.get("loss", loss), "lr": lr,
                       "grad_norm": gnorm}
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, out_metrics)

    return train_step


# ---------------------------------------------------------------------------
# explicit-DP shard_map step: the paper's schemes as collective schedules
# ---------------------------------------------------------------------------

def make_dp_train_step(api: ModelApi, optimizer: Optimizer,
                       lr_schedule: Callable, mesh, *,
                       grad_scheme: str = "arena",
                       compress: bool = False) -> Callable:
    """Replicated-params data parallelism with explicit gradient collectives.

    grad_scheme:
      "pertensor"  one psum per gradient leaf (the per-leaf deep copy)
      "arena"      pack gradients into per-dtype contiguous buckets, ONE
                   psum per bucket (marshalling on the interconnect)
    compress=True  int8 + error-feedback on the arena payload before psum
                   (collective bytes /4); only with grad_scheme="arena".
    """
    if compress and grad_scheme != "arena":
        raise ValueError("compression requires the arena scheme")
    cfg = api.cfg
    axis = "data"

    dp_size = int(mesh.shape[axis])
    # the gradient arena's transfer policy as a spec: marshalling arena,
    # 128-element alignment for DMA/collective efficiency, buckets padded
    # per dp shard — the same declarative axes the transfer schemes use.
    grad_spec = grad_arena_spec(dp_size)

    def grad_sync(grads, error_state):
        if grad_scheme == "pertensor":
            return (jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, axis), grads), error_state)
        # gradient arena via the persistent engine: the layout is planned
        # once per treedef (session cache shared with the transfer schemes)
        # and the pack/unpack lower to one fused scatter/gather region per
        # bucket.  Sharding the plan by the dp degree pads every bucket to
        # a per-device multiple, so the collective payload chunks evenly
        # across the axis (reduce-scatter-ready; per-device arena layout).
        layout = engine_lib.get_session().plan(grads, grad_spec)
        buffers = engine_lib.pack_traced(grads, layout)
        if compress:
            # exact shared-scale int8 all-reduce with error feedback:
            # 1) agree on per-chunk scale via a (tiny) max-psum;
            # 2) every rank quantizes (grad+err) with the SHARED scale;
            # 3) psum the int8 payload (int32 accumulation in simulation —
            #    real deployment reduces in s8/s16 hierarchically);
            # 4) residual goes to the error-feedback buffer.
            new_err = {}
            synced = {}
            C = compression.CHUNK
            for bucket, buf in buffers.items():
                if bucket not in error_state:
                    synced[bucket] = jax.lax.psum(buf, axis)
                    continue
                n = buf.shape[0]
                corrected = (compression._pad_to(buf.astype(jnp.float32), C)
                             + error_state[bucket])
                chunks = corrected.reshape(-1, C)
                local_max = jnp.max(jnp.abs(chunks), axis=1)
                scale = jax.lax.pmax(local_max, axis) / 127.0 + 1e-12
                q = jnp.clip(jnp.round(chunks / scale[:, None]), -127, 127)
                qsum = jax.lax.psum(q.astype(jnp.int32), axis)
                out = (qsum.astype(jnp.float32) * scale[:, None]).reshape(-1)
                synced[bucket] = out[:n].astype(buf.dtype)
                new_err[bucket] = (chunks - q * scale[:, None]).reshape(-1)
            return engine_lib.unpack_traced(synced, layout), new_err
        # one all-reduce per bucket.  XLA lowers an all-reduce to its own
        # reduce-scatter + all-gather schedule, and its all-reduce combiner
        # merges the bucket all-reduces with the loss's into one collective;
        # an explicit psum_scatter + all_gather pair cannot be combined and
        # costs two collectives per bucket.
        synced = {b: jax.lax.psum(buf, axis) for b, buf in buffers.items()}
        return engine_lib.unpack_traced(synced, layout), error_state

    def step_fn(state, batch, error_state):
        params = state["params"]
        (loss, metrics), grads = jax.value_and_grad(
            lambda p, b: api.loss_fn(p, b), has_aux=True)(params, batch)
        grads, error_state = grad_sync(grads, error_state)
        loss = jax.lax.pmean(loss, axis)
        lr = lr_schedule(state["step"])
        new_params, new_opt = optimizer.update(grads, state["opt"], params, lr)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, "lr": lr}, error_state

    replicated = P()
    batch_spec = P(axis)

    def shape_spec(tree, spec):
        return jax.tree_util.tree_map(lambda _: spec, tree)

    def wrapped(state, batch, error_state):
        fn = jax.shard_map(
            step_fn, mesh=mesh,
            in_specs=(shape_spec(state, replicated),
                      shape_spec(batch, batch_spec),
                      shape_spec(error_state, replicated)),
            out_specs=(shape_spec(state, replicated),
                       {"loss": replicated, "lr": replicated},
                       shape_spec(error_state, replicated)),
            check_vma=False)
        return fn(state, batch, error_state)

    return jax.jit(wrapped)


def grad_arena_spec(dp_size: int = 1) -> TransferSpec:
    """The gradient arena's policy point: one spec shared by the dp train
    step and the error-feedback state so their plans are the SAME session
    cache entry."""
    return TransferSpec("marshal", align_elems=128, sharding=int(dp_size))


def state_transfer_policy(dp_size: int = 1):
    """The train-state placement policy, as ONE path-scoped policy tree:
    params land in the 128-aligned (dp-sharded) persistent arena the
    gradient collective also uses, optimizer state moves incrementally
    (delta — after a restore or host-side edit only the touched buckets
    re-ship), and everything else (step counters, metadata) is plainly
    marshalled."""
    from ..core.policy import TransferPolicy

    return TransferPolicy.parse(
        f"params/**=marshal+align128@dp{int(dp_size)}; "
        "opt/**=marshal+delta; **=marshal")


def replicate_state(state: Any, num_devices: int) -> Any:
    """Replicate every leaf onto the first ``num_devices`` devices (``P()``
    over the default 1-D data mesh).

    The elastic-restore hand-off: a sharded state policy stages the
    checkpoint as per-device sub-ranges (the measured deep copy — each
    device DMAs 1/k of every bucket), but this repo's data-parallel step
    (`make_dp_train_step`) computes on REPLICATED params.  Re-placing the
    staged tree onto one consistent mesh makes the restored state legal
    input for any single jitted step — the staged regions would otherwise
    sit on different device sets (params on the dp mesh, delta regions on
    device 0) — and keeps the resumed trajectory bit-identical: replication
    is a copy, not arithmetic."""
    if num_devices <= 1:
        return state
    from jax.sharding import NamedSharding, PartitionSpec

    from ..jaxenv import make_mesh

    mesh = make_mesh((num_devices,), ("data",))
    target = NamedSharding(mesh, PartitionSpec())
    return jax.tree_util.tree_map(  # lint: allow=DC201 -- one-shot init placement
        lambda l: jax.device_put(l, target), state)


def compile_state_program(state: Dict[str, Any], dp_size: int = 1,
                          session=None):
    """Compile the state policy against a concrete train-state tree — the
    single program `runtime.loop` stages restored checkpoints through."""
    session = session if session is not None else engine_lib.get_session()
    return session.compile(state, state_transfer_policy(dp_size))


class StatePrefetcher:
    """Step-level state prefetch over a compiled TransferProgram.

    The discipline ``data.pipeline.Prefetcher`` applies to batches, applied
    to state motion: while step N's compute runs, :meth:`schedule` stages
    step N+1's (dirty) host state through the arena's spare double-buffer —
    pack + enqueue-all happen immediately on the caller's thread, the
    single sync rides a background thread (``TransferProgram.
    to_device_async``) — and :meth:`take` materializes the staged device
    tree right when the step needs it.  With compute longer than the DMA,
    ``take`` returns without waiting: the transfer left the critical path.

    Delta regions keep their meaning: pass ``dirty_paths`` to re-ship only
    the buckets a host-side mutator touched.  The program's depth-1
    pipeline makes back-to-back schedules safe (the engine drains the
    in-flight pass before re-packing a staging buffer)."""

    def __init__(self, program):
        self.program = program
        self._future = None

    @property
    def scheduled(self) -> bool:
        return self._future is not None

    def schedule(self, host_state: Any, *dirty_paths: str):
        """Begin staging ``host_state`` (only ``dirty_paths``' buckets for
        delta regions, everything if none given); returns the future."""
        if dirty_paths:
            self.program.mark_dirty(host_state, *dirty_paths)
        self._future = self.program.to_device_async(host_state)
        return self._future

    def take(self) -> Any:
        """The staged device tree for the step about to run (waits only the
        residual DMA, zero in steady state)."""
        if self._future is None:
            raise RuntimeError("StatePrefetcher.take() with nothing "
                               "scheduled — call schedule() first")
        future, self._future = self._future, None
        return future.result()


def init_error_state(api: ModelApi, compress: bool,
                     mesh=None) -> Dict[str, Any]:
    if not compress:
        return {}
    params = api.abstract()
    # gradients carry the parameter dtype; same cached plan the dp step
    # uses, INCLUDING the per-device padding when the mesh is known (the
    # error-feedback buffers must match the padded bucket sizes exactly).
    dp_size = int(mesh.shape["data"]) if mesh is not None else 1
    layout = engine_lib.get_session().plan(params, grad_arena_spec(dp_size))
    pad = lambda n: -(-n // compression.CHUNK) * compression.CHUNK
    return {b: jnp.zeros((pad(n),), jnp.float32)
            for b, n in layout.bucket_sizes.items()}
