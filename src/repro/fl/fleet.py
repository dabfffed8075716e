"""Vectorized client-fleet execution engine.

The sequential reference path (`FluidServer` + `SimClient.train`) dispatches
one jit call per client and gives every straggler a physically smaller
sub-model — so each new dropout rate means a new set of array shapes and a
recompile, and round time scales with the Python loop, not the hardware.

This engine runs the *entire cohort* as one compiled program:

  * Sub-models become dense keep-masks (core/submodel.keep_mask) applied
    inside the batched train step — the masking idiom of
    kernels/masked_ffn.py lifted to whole param trees. forward(mask*params)
    equals forward(extract(params)) on the kept coordinates because every
    consumer weight of a dropped neuron is zeroed, so full-model clients and
    every dropout rate share ONE compiled shape; the mask is data, not
    shape.
  * Local SGD for all C clients is one jax.lax.map over the clients of a
    jax.lax.scan of minibatches: each client's steps run at its own batch
    shape inside the one program (see _cohort_fn for why not jax.vmap).
    Shards of different sizes pad to the cohort-max step count and batch
    size; padding is neutralized by per-sample loss weights.
  * Client samples live on the device: the engine uploads every client's
    rows once, as one (C, rows, ...) array, and each round sends only the
    (C, steps, bs) sample order, labels and weights. The program gathers
    each client's batches from its rows by that order.
  * Hyperparameters are data too: learning rates are a mapped (C,) array
    and per-client step counts ride on the same zero-weight padding that
    absorbs ragged shards, so heterogeneous (lr, local-epochs) cohorts —
    and the serving engine's per-request sub-models (launch/serving.py) —
    share one compiled program with the uniform case.
  * Gradients are mask-projected each step, so deltas come back already
    mask-zeroed in full coordinates — exactly what embed_delta() would have
    produced — and aggregation collapses to one fused device-side
    tree-reduce (core/aggregate.aggregate_stacked) instead of per-update
    Python arithmetic.
  * Masks are deduplicated into a (K, ...) bank (core/maskbank.MaskBank:
    all-ones row 0 + one row per straggler keep-map) indexed per client, so
    mask memory scales with the number of *distinct* sub-models, not the
    fleet size.

Numerical contract (tests/test_fleet.py): with the same seeds, a fleet
round reproduces the sequential round's deltas, sim-times, and aggregated
params up to float summation order — including cohorts with per-client
(lr, local-epochs).
"""
from __future__ import annotations

import functools

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import invariant as inv
from repro.core import spans
from repro.core import submodel as sub
from repro.core.aggregate import ClientUpdate, aggregate_stacked
from repro.core.maskbank import MaskBank
from repro.fl.client import (FleetClient, make_weighted_kernel_loss,
                             make_weighted_loss)
from repro.kernels import ops as kops

_COHORT_CACHE: Dict[tuple, callable] = {}

# Small step counts unroll fully in Python; longer ones use scan's unroll
# knob on the CPU (a speed-up for the CPU tests' cohort programs). On a
# TPU the knob only multiplies compile time (the FEMNIST CNN cohort program
# at C=32 compiles for a v5e in half the time at unroll 1), so other
# backends keep the plain loop.
_FULL_UNROLL_STEPS = 16
_SCAN_UNROLL_CPU = 8


def _scan_unroll() -> int:
    return _SCAN_UNROLL_CPU if jax.default_backend() == "cpu" else 1


def _cohort_fn(model_cls, use_kernels: bool = False):
    """One compiled program: masked local SGD for a whole cohort.

    The clients are mapped with jax.lax.map, one client's scan at a time,
    and not batched with jax.vmap. Under vmap every convolution with
    per-client weights becomes a grouped convolution over the cohort, and
    on a TPU v5e at default precision some of those come out wrong: at 64
    clients the VGG-9's c1a, c3a and c3b weight gradients differ from the
    exact ("highest") gradient by 62-151% of its norm, against 13-22% for
    the same convolutions run per client (one bfloat16 pass through nine
    layers). Per-client convolutions are also the faster form there (a
    VGG-9 gradient step for 64 clients: 15.1 ms mapped, 26.3 ms vmapped;
    FEMNIST CNN at 128: 9.8 ms and 10.9 ms).

    use_kernels routes every forward/backward through the model's
    `apply_kernels` Pallas path (models/kernel_models.py): dropped
    128-blocks/heads are *skipped* via the custom_vjp kernels of
    DESIGN.md §10 instead of multiplied by zero, so a rate-r straggler does
    ~r of the FLOPs. Numerically equivalent to the dense path (the skipped
    activations are act(0) = 0 and the skipped dW tiles are exact zeros) —
    enforced by tests/test_kernel_grad.py. The cache is keyed on the
    kernels' interpret mode (kernels/ops.py), which the trace bakes in."""
    key = (model_cls.__name__, use_kernels, kops._default_interpret())
    if key not in _COHORT_CACHE:
        if use_kernels:
            kloss = make_weighted_kernel_loss(model_cls)
        loss = make_weighted_loss(model_cls)

        @functools.partial(jax.jit, static_argnames=("n_steps",))
        def run(params, mask_bank, mask_idx, client_x, order, ys, sw, lrs,
                n_steps):
            """params: full tree (broadcast); mask_bank: (K, ...) leaves;
            mask_idx: (C,); client_x: (C, rows, ...) every client's samples;
            order: (C, S, bs) int32 rows of client_x, batch by batch;
            ys: (C, S, bs); sw: (C, S, bs) per-sample weights — 1.0 on real
            samples, 0.0 on batch/step padding (an all-zero step is a
            no-op); lrs: (C,) per-client learning rates (hyperparameters
            are data — heterogeneous cohorts don't re-specialize the
            program). Returns mask-zeroed full-coordinate deltas, (C, ...)
            leaves."""
            def one_client(mi, cx, o, y, v, lr):
                # the client's whole (S, bs, ...) block, gathered once
                # before its step scan and not step by step
                x = cx[o]
                m = jax.tree.map(lambda b: b[mi], mask_bank)
                w0 = sub.apply_mask(params, m)
                if use_kernels:
                    kmasks = model_cls.kernel_masks(m)

                def step(w, batch):
                    xb, yb, vb = batch
                    if use_kernels:
                        g = jax.grad(kloss)(w, xb, yb, vb, kmasks)
                    else:
                        g = jax.grad(loss)(w, xb, yb, vb)
                    return jax.tree.map(
                        lambda a, ga, ma: a - lr * ma * ga,
                        w, g, m), 0
                if n_steps <= _FULL_UNROLL_STEPS:
                    w = w0
                    for s in range(n_steps):
                        w, _ = step(w, (x[s], y[s], v[s]))
                else:
                    w, _ = jax.lax.scan(step, w0, (x, y, v),
                                        unroll=_scan_unroll())
                # every update step carried the mask factor => pre-zeroed
                return jax.tree.map(lambda a, b: a - b, w, w0)
            return jax.lax.map(lambda a: one_client(*a),
                               (mask_idx, client_x, order, ys, sw, lrs))
        _COHORT_CACHE[key] = run
    return _COHORT_CACHE[key]


@dataclass
class CohortResult:
    """Stacked outputs of one fleet round + lazy per-client views."""
    engine: "FleetEngine"
    deltas: dict                    # tree of (C, ...) leaves, mask-zeroed
    weights: jnp.ndarray            # (C,) sample counts
    mask_bank: dict                 # tree of (K, ...) leaves
    mask_idx: jnp.ndarray           # (C,) int32
    client_ids: List[int]
    sim_times: Dict[int, float]
    straggler_ids: frozenset
    members: Optional[np.ndarray] = None   # (C,) bool; None = all real

    def _is_member(self, i: int) -> bool:
        return self.members is None or bool(self.members[i])

    def aggregate(self, global_params):
        """Fused device-side masked FedAvg (== core.aggregate.aggregate).
        Padding slots (members[i] == False) carry zero weight AND zero
        deltas (their step count is 0), so they cancel out of both the
        numerator and the per-mask denominator."""
        return aggregate_stacked(global_params, self.deltas, self.weights,
                                 self.mask_bank, self.mask_idx)

    def non_straggler_stats(self, prev_params) -> List[Dict[str, np.ndarray]]:
        """Per-client invariant-neuron stats, computed batched on device
        and copied to the host one (N, size) array per unit group."""
        sel = np.array([i for i, cid in enumerate(self.client_ids)
                        if cid not in self.straggler_ids
                        and self._is_member(i)], dtype=np.int32)
        if sel.size == 0:
            return []
        with spans.span("fleet.stats_wait"):
            # the host's wait on local SGD alone, before the gather and
            # the stats program are queued behind it
            with spans.span("fleet.cohort_wait"):
                jax.block_until_ready(self.deltas)
            picked = jax.tree.map(lambda d: d[sel], self.deltas)
            stacked = self.engine._stats_fn(prev_params, picked)
            # the copy below waits on this anyway (and with it on the
            # cohort program): the wait is timed here, not there
            jax.block_until_ready(stacked)
        # one device-to-host copy per unit group; each client's row is a
        # view into the group's (N, size) host array
        spans.count("fleet.stats_copies", len(stacked))
        with spans.span("fleet.stats_copy"):
            host = jax.device_get(stacked)
        return [{g: v[i] for g, v in host.items()} for i in range(sel.size)]

    def updates(self) -> List[ClientUpdate]:
        """Materialize sequential-style ClientUpdates (tests / inspection)."""
        out = []
        for i, cid in enumerate(self.client_ids):
            if not self._is_member(i):
                continue
            delta = jax.tree.map(lambda d: d[i], self.deltas)
            mask = None
            if cid in self.straggler_ids:
                row = int(self.mask_idx[i])
                mask = jax.tree.map(lambda b: b[row], self.mask_bank)
            out.append(ClientUpdate(delta, int(self.weights[i]), mask,
                                    self.sim_times[cid], cid))
        return out


class FleetEngine:
    """Runs a homogeneous-model client fleet as single compiled programs.

    The model architecture is uniform across the cohort (one param tree
    shape); per-client hyperparameters (lr, local epochs / step counts) and
    per-client sub-model masks are mapped data, not program structure.
    """

    def __init__(self, model_cls, clients: Sequence[FleetClient], unit_specs,
                 use_kernels: bool = False):
        self.model_cls = model_cls
        self.clients = list(clients)
        self.unit_specs = unit_specs
        self.use_kernels = bool(use_kernels)
        if not self.clients:
            raise ValueError("FleetEngine needs at least one client")
        if self.use_kernels and not hasattr(model_cls, "apply_kernels"):
            raise ValueError(
                f"use_kernels=True needs a model exposing apply_kernels / "
                f"kernel_masks (see models/kernel_models.py); "
                f"{model_cls.__name__} does not")
        # batch dim pads to the cohort max; smaller shards get sample weights
        self.bs = max(c.eff_batch_size for c in self.clients)
        self.client_steps = np.array(
            [c.local_epochs * (c.n_samples // c.eff_batch_size)
             for c in self.clients], np.int32)
        self.steps = int(self.client_steps.max())
        # rows of the resident client data: the largest shard, rounded up
        # past its last full batch, so cohorts whose shards give the same
        # step count give one shape
        n_max = max(c.n_samples for c in self.clients)
        self.rows = (n_max // self.bs + 1) * self.bs
        self.lrs = np.array([c.lr for c in self.clients], np.float32)
        self._run = _cohort_fn(model_cls, self.use_kernels)
        self._client_x = None          # device (C, rows, ...), on first use
        self._ones_mask: Optional[dict] = None
        self._stats_jit = None
        self._bank_cache = None        # (fingerprint, bank, idx, n_by_row)

    # ------------------------------------------------------------- internals
    def _stats_fn(self, prev, stacked_deltas):
        if self._stats_jit is None:
            specs = self.unit_specs

            def one(prev_p, d):
                new = jax.tree.map(lambda a, b: a + b, prev_p, d)
                return inv.neuron_stats(prev_p, new, specs)
            self._stats_jit = jax.jit(
                lambda p, ds: jax.vmap(lambda d: one(p, d))(ds))
        return self._stats_jit(prev, stacked_deltas)

    def _put_client_data(self, x: np.ndarray):
        return jnp.asarray(x)

    def _client_data(self):
        """Every client's samples as one device array (C, rows, ...), in the
        clients' own dtype, rows past a client's shard zero. Uploaded once,
        on first use, in the `fleet.data_upload` span and counted by the
        `fleet.data_uploads` counter; the clients' data never changes."""
        if self._client_x is None:
            spans.count("fleet.data_uploads")
            with spans.span("fleet.data_upload"):
                first = self.clients[0].x
                x = np.zeros((len(self.clients), self.rows, *first.shape[1:]),
                             first.dtype)
                for i, c in enumerate(self.clients):
                    x[i, :c.n_samples] = c.x
                self._client_x = self._put_client_data(x)
        return self._client_x

    def _stacked_data(self, n_steps: Optional[np.ndarray] = None):
        """(order, ys, sw), each (C, steps, bs): the rows of each client's
        resident data (`_client_data`) that its epoch batches take, padded
        to (steps, bs) with row 0; the labels of those samples; and sw, 1.0
        on real samples, 0.0 on batch/step padding. Consumes each client's
        RNG exactly like SimClient.train. n_steps (C,) caps the number of
        *real* SGD steps per client by zero-weighting the tail — step
        counts are data riding on the same padding as ragged shards.

        Built on the host every round (only the permutations change) and
        copied to the device, ~12 bytes a sample slot; the `fleet.stage`
        span (benchmark metric `stage_ms`) times both."""
        with spans.span("fleet.stage"):
            C = len(self.clients)
            order = np.zeros((C, self.steps, self.bs), np.int32)
            ys = np.zeros((C, self.steps, self.bs), np.int32)
            sw = np.zeros((C, self.steps, self.bs), np.float32)
            for i, c in enumerate(self.clients):
                o = c.local_orders()
                s, b = o.shape
                order[i, :s, :b] = o
                ys[i, :s, :b] = c.y[o]
                sw[i, :s, :b] = 1.0
                if n_steps is not None:
                    sw[i, int(n_steps[i]):] = 0.0
            return jnp.asarray(order), jnp.asarray(ys), jnp.asarray(sw)

    def _mask_bank(self, params, keep_maps: Dict[int, dict]):
        """(bank, idx, n_params_by_row): all-ones row 0 + one row per
        *distinct* straggler keep-map (core/maskbank.MaskBank dedupe); idx
        maps client position -> bank row. Cached across rounds while the
        keep-maps are unchanged (they only move on calibration steps)."""
        km_fp = {cid: tuple((g, kept.tobytes())
                            for g, kept in sorted(km.items()))
                 for cid, km in keep_maps.items()}
        fp = tuple(sorted(km_fp.items()))
        if self._bank_cache is not None and self._bank_cache[0] == fp:
            return self._bank_cache[1:]
        if self._ones_mask is None:
            self._ones_mask = jax.tree.map(
                lambda p: jnp.ones(p.shape, jnp.float32), params)
        bank_obj = MaskBank(self._ones_mask)
        row_of = {cid: bank_obj.row_for(
            km_fp[cid],
            functools.partial(sub.keep_mask, params, self.unit_specs,
                              keep_maps[cid]))
            for cid in sorted(keep_maps)}
        bank = bank_obj.stacked()
        idx = jnp.asarray([row_of.get(c.id, 0) for c in self.clients],
                          jnp.int32)
        # exact integer param counts per row (per-leaf int32 sums of a 0/1
        # mask cannot overflow; accumulate in host int64 across leaves)
        n_by_row = sum(
            np.asarray(b.sum(axis=tuple(range(1, b.ndim)),
                             dtype=jnp.int32)).astype(np.int64)
            for b in jax.tree.leaves(bank))
        self._bank_cache = (fp, bank, idx, n_by_row)
        return bank, idx, n_by_row

    def _execute(self, params, bank, idx, order, ys, sw, lrs, weights):
        """Run the cohort program on the clients' resident data, in the
        sample order of `_stacked_data`. Returns (deltas, extra): extra is
        None here; the sharded subclass (fl/shard_fleet.py) returns the
        hierarchically-reduced aggregation partials instead of recomputing
        them from the gathered deltas."""
        return self._run(params, bank, idx, self._client_data(), order, ys,
                         sw, lrs, self.steps), None

    def _wrap_result(self, extra, **kw) -> "CohortResult":
        return CohortResult(**kw)

    # ------------------------------------------------------------------- API
    def run_cohort(self, params, keep_maps: Dict[int, dict],
                   rates: Optional[Dict[int, float]] = None,
                   lr=None, n_steps=None, members=None) -> CohortResult:
        """One FL round for the whole fleet: keep_maps/rates per straggler
        client id (absent => full model).

        lr: optional scalar or (C,) array overriding the clients' own
        learning rates; n_steps: optional (C,) int array capping each
        client's real SGD steps. Both are mapped data — heterogeneous
        values reuse the same compiled program as the uniform cohort.

        members: optional (C,) bool marking which slots are real clients —
        partial-cohort execution for callers that must keep the program
        shape capacity-padded while dispatching fewer than C clients
        (fl/async_rounds.py pads every dispatch group to buffer_k). A
        padding slot runs 0 SGD steps (all its sample weights are zero, so
        its delta is exactly zero), carries zero aggregation weight, draws
        no sim time (its RNG stream is never touched), and is excluded
        from stats and updates()."""
        rates = rates or {}
        C = len(self.clients)
        if lr is None:
            lrs = self.lrs
        else:
            lrs = np.broadcast_to(np.asarray(lr, np.float32), (C,))
        if n_steps is not None:
            n_steps = np.asarray(n_steps, np.int32)
            if n_steps.shape != (C,):
                raise ValueError(f"n_steps must be ({C},), "
                                 f"got {n_steps.shape}")
        if members is not None:
            members = np.asarray(members, bool)
            if members.shape != (C,):
                raise ValueError(f"members must be ({C},), "
                                 f"got {members.shape}")
            base_steps = self.client_steps if n_steps is None else n_steps
            n_steps = np.where(members, base_steps, 0).astype(np.int32)
        order, ys, sw = self._stacked_data(n_steps)
        bank, idx, n_by_row = self._mask_bank(params, keep_maps)
        w_host = np.asarray([c.n_samples for c in self.clients], np.float32)
        if members is not None:
            w_host = np.where(members, w_host, 0.0).astype(np.float32)
        weights = jnp.asarray(w_host)
        deltas, extra = self._execute(params, bank, idx, order, ys, sw,
                                      jnp.asarray(lrs), weights)
        idx_host = np.asarray(idx)
        sim_times = {
            c.id: c.draw_sim_time(rates.get(c.id, 1.0),
                                  int(n_by_row[idx_host[i]]))
            for i, c in enumerate(self.clients)
            if members is None or members[i]}
        return self._wrap_result(
            extra, engine=self, deltas=deltas, weights=weights,
            mask_bank=bank, mask_idx=idx,
            client_ids=[c.id for c in self.clients], sim_times=sim_times,
            straggler_ids=frozenset(keep_maps), members=members)
