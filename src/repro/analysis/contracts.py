"""Trace-time contract checker (pass 2 of repro.analysis).

Where lint.py reads source text, this pass traces the real programs —
``jax.make_jaxpr`` / ``jax.eval_shape`` / actual jit calls — and asserts the
invariants the repo's performance story depends on:

  * **no-f64**: traced under ``jax.enable_x64()`` (which makes
    every implicit float64 promotion visible as an f64 outvar instead of
    being silently truncated to f32), the train step of every zoo arch, the
    paper-scale model grads, and all three optimizers produce no float64
    values. The same jaxpr walk rejects host-callback primitives — nothing
    in a hot path may sync back to Python.
  * **single-trace**: "the mask is data, not shape" (DESIGN.md §8). The
    masked train step, the fleet cohort program, and the ServeEngine's three
    compiled bodies must each trace exactly once across different mask-bank
    contents and mixed per-client (lr, n_steps) hyperparameters. Measured
    with ``jax.jit``'s ``_cache_size`` and ServeEngine.trace_counts, not
    inferred.
  * **dropped-dW-zero**: the structural guarantee of DESIGN.md §10. Dropped
    weight tiles are poisoned with NaN; the forward must stay finite and the
    dropped blocks'/heads' weight cotangents must come back bitwise zero —
    proof the kernels never read or write those tiles, for every distinct
    128-aligned FFN width and head count in configs/.

Init functions are NOT traced under x64: ``jax.random.normal`` defaults to
f64 there by design and every init astypes to the param dtype immediately;
the static factory-dtype rule (lint FLD104) covers init-time discipline.

Checks return lists of :class:`Violation`; ``run_contracts()`` runs the
whole registry (unexpected exceptions become violations, not crashes).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F64 = np.dtype("float64")

# host-sync primitives that must not appear in any hot-path jaxpr
CALLBACK_PRIMITIVES = {"pure_callback", "io_callback", "callback",
                       "debug_callback", "python_callback"}


@dataclass
class Violation:
    check: str          # registry key, e.g. "no-f64-zoo"
    where: str          # traced entity, e.g. "train_step[stablelm-12b]"
    message: str

    def __str__(self):
        return f"{self.check}: {self.where}: {self.message}"


# ---------------------------------------------------------------------------
# jaxpr walking

def _iter_subjaxprs(params: dict):
    def as_jaxpr(v):
        if hasattr(v, "eqns"):
            return v                            # raw Jaxpr
        if hasattr(v, "jaxpr"):
            return v.jaxpr                      # ClosedJaxpr
        return None

    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for w in vs:
            jx = as_jaxpr(w)
            if jx is not None:
                yield jx


def walk_jaxpr(jaxpr) -> Dict[str, List[str]]:
    """Collect f64-producing equations and callback primitives, recursing
    into every sub-jaxpr carried in eqn params (scan/cond/jit bodies)."""
    hits = {"f64": [], "callback": []}

    def visit(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in CALLBACK_PRIMITIVES:
                hits["callback"].append(name)
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None and dt == F64:
                    hits["f64"].append(f"{name} -> {aval.str_short()}")
            for sub in _iter_subjaxprs(eqn.params):
                visit(sub)

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return hits


def _trace_violations(check: str, where: str, fn, *args) -> List[Violation]:
    """Trace fn under x64 and convert walk hits into Violations."""
    from jax import enable_x64
    with enable_x64():
        jaxpr = jax.make_jaxpr(fn)(*args)
    hits = walk_jaxpr(jaxpr)
    out = []
    for h in hits["f64"][:5]:
        out.append(Violation(check, where,
                             f"float64 value in traced program: {h}"))
    if len(hits["f64"]) > 5:
        out.append(Violation(check, where,
                             f"... {len(hits['f64']) - 5} more f64 values"))
    for h in sorted(set(hits["callback"])):
        out.append(Violation(check, where,
                             f"host callback primitive '{h}' under jit"))
    return out


# ---------------------------------------------------------------------------
# input spec helpers

def _zoo_batch_spec(cfg, batch=2, seq=8):
    s = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
         "targets": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if cfg.is_encdec:
        s["frames"] = jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                           jnp.dtype(cfg.dtype))
    return s


def _model_batch(model_cls, batch=2):
    """Concrete (x, y) for a paper-scale model; LSTM takes int tokens."""
    if model_cls.__name__ == "ShakespeareLSTM":
        x = jnp.zeros((batch, model_cls.seq_len), jnp.int32)
    else:
        x = jnp.zeros((batch, *model_cls.input_shape), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)
    return x, y


# ---------------------------------------------------------------------------
# no-f64 / no-callback checks

def check_zoo_train_no_f64() -> List[Violation]:
    """Trace make_train_step for every configs/ arch under x64."""
    from repro.configs.base import all_configs
    from repro.launch.steps import make_train_step
    from repro.models import model as model_lib
    from repro.optim import make_optimizer
    out = []
    for arch, cfg in all_configs().items():
        cfg = cfg.smoke().with_overrides(grad_accum=1)
        params = jax.eval_shape(
            functools.partial(model_lib.init_params, cfg),
            jax.random.PRNGKey(0))
        opt_state = jax.eval_shape(make_optimizer(cfg.optimizer).init, params)
        step = make_train_step(cfg)
        out += _trace_violations("no-f64-zoo", f"train_step[{arch}]",
                                 step, params, opt_state,
                                 _zoo_batch_spec(cfg))
    return out


def check_models_no_f64() -> List[Violation]:
    """Trace grads of the paper-scale + kernel fleet models under x64."""
    from repro.fl.client import make_weighted_loss
    from repro.models.kernel_models import KERNEL_MODELS
    from repro.models.small import MODELS
    out = []
    for name, cls in {**MODELS, **KERNEL_MODELS}.items():
        x, y = _model_batch(cls)
        v = jnp.ones(y.shape, jnp.float32)
        loss = make_weighted_loss(cls)
        out += _trace_violations("no-f64-models", f"grad[{name}]",
                                 jax.grad(loss),
                                 jax.eval_shape(cls.init,
                                                jax.random.PRNGKey(0)),
                                 x, y, v)
    return out


def check_optim_no_f64() -> List[Violation]:
    """Trace every optimizer's update under x64 on a small f32 tree."""
    from repro.optim import make_optimizer
    params = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32),
              "b": jax.ShapeDtypeStruct((4,), jnp.float32)}
    out = []
    for name in ("sgd", "sgdm", "adamw"):
        opt = make_optimizer(name)
        state = jax.eval_shape(opt.init, params)
        out += _trace_violations(
            "no-f64-optim", f"update[{name}]",
            lambda g, s, p: opt.update(g, s, p, 0.01), params, state, params)
    return out


# ---------------------------------------------------------------------------
# single-trace checks

def check_train_step_single_trace(arch="stablelm-12b") -> List[Violation]:
    """The masked train step compiles once across mask contents."""
    from repro.configs.base import get_config
    from repro.core import transformer_hooks as hooks
    from repro.launch.serving import rate_masks
    from repro.launch.steps import make_train_step
    from repro.models import model as model_lib
    from repro.optim import make_optimizer
    cfg = get_config(arch).smoke().with_overrides(grad_accum=1)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer(cfg.optimizer)
    opt_state = opt.init(params)
    # the probe reuses params/opt_state across calls, so nothing is donatable
    step = jax.jit(make_train_step(cfg, with_masks=True))  # fluidlint: disable=FLD107
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, 64, (2, 9))[:, :-1],
                                   dtype=jnp.int32),
             "targets": jnp.asarray(rng.randint(0, 64, (2, 9))[:, 1:],
                                    dtype=jnp.int32)}
    for masks in (hooks.full_masks(cfg), rate_masks(cfg, 0.5),
                  rate_masks(cfg, 0.75, policy="random")):
        params, opt_state, _ = step(params, opt_state, batch, masks)
    n = step._cache_size()
    if n != 1:
        return [Violation("single-trace-train",
                          f"make_train_step[{arch}, with_masks]",
                          f"{n} traces across 3 mask contents (want 1): "
                          f"a mask shape or dtype is leaking into the "
                          f"program structure")]
    return []


def check_fleet_single_trace() -> List[Violation]:
    """One cohort program across rounds with different mask-bank contents
    and mixed per-client (lr, n_steps) hyperparameters."""
    from repro.fl.client import FleetClient
    from repro.fl.fleet import FleetEngine
    from repro.models.small import FemnistCNN
    rng = np.random.RandomState(0)
    clients = [
        FleetClient(id=i, model_cls=FemnistCNN,
                    x=rng.randn(n, 28, 28, 1).astype(np.float32),
                    y=rng.randint(0, 62, (n,)).astype(np.int32),
                    speed=1.0, batch_size=20, local_epochs=1,
                    lr=0.01, seed=0)
        for i, n in enumerate((60, 40, 60, 40))]
    engine = FleetEngine(FemnistCNN, clients, FemnistCNN.UNIT_SPECS)
    params = FemnistCNN.init(jax.random.PRNGKey(0))
    before = engine._run._cache_size()

    def km(c1, c2, f1):
        return {"conv1": np.arange(c1), "conv2": np.arange(c2),
                "fc1": np.arange(f1)}

    # The bank's ROW COUNT is shape (it only changes on calibration steps);
    # mask CONTENTS, row assignment, and hyperparameters are data. Hold the
    # number of distinct masks at 2 across both rounds and vary everything
    # else — the cohort program must not re-specialize.
    # round 1: two stragglers, uniform hyperparameters
    engine.run_cohort(params, {0: km(12, 48, 90), 1: km(8, 32, 60)},
                      rates={0: 0.75, 1: 0.5})
    # round 2: different mask contents + mixed lr and per-client step counts
    engine.run_cohort(params, {0: km(10, 40, 80), 2: km(14, 56, 100)},
                      rates={0: 0.6, 2: 0.9},
                      lr=np.array([0.01, 0.02, 0.005, 0.01], np.float32),
                      n_steps=np.array([1, 2, 1, 2], np.int32))
    delta = engine._run._cache_size() - before
    if delta != 1:
        return [Violation("single-trace-fleet", "FleetEngine.run_cohort",
                          f"{delta} traces across 2 heterogeneous rounds "
                          f"(want 1): masks or hyperparameters are "
                          f"re-specializing the cohort program")]
    return []


def check_serve_single_trace(arch="stablelm-12b") -> List[Violation]:
    """ServeEngine's prefill/insert/decode each trace once over a queue of
    mixed dropout rates, prompt lengths, and generation lengths."""
    from repro.configs.base import get_config
    from repro.launch.serving import ServeEngine, ServeRequest, rate_masks
    from repro.models import model as model_lib
    cfg = get_config(arch).smoke()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_size=2, max_prompt_len=8,
                      max_gen_len=4, chunk=2, bank_size=4)
    rng = np.random.RandomState(0)

    def prompt(n):
        return rng.randint(0, 64, (n,)).astype(np.int32)

    eng.submit(ServeRequest(tokens=prompt(8), gen_len=4, masks=None))
    eng.submit(ServeRequest(tokens=prompt(5), gen_len=3,
                            masks=rate_masks(cfg, 0.5)))
    eng.submit(ServeRequest(tokens=prompt(7), gen_len=4,
                            masks=rate_masks(cfg, 0.75, policy="random")))
    eng.run()
    out = []
    for k, n in eng.trace_counts.items():
        if n != 1:
            out.append(Violation(
                "single-trace-serve", f"ServeEngine.{k}[{arch}]",
                f"traced {n} times over a mixed-rate queue (want 1)"))
    return out


def check_population_single_trace() -> List[Violation]:
    """The sharded cohort program compiles once across population rounds:
    each round samples a DIFFERENT cohort from the store, but shard shapes
    are constant (equal-size partitions), so the (S, Cs, ...) program must
    never re-trace. Uses policy='none' to hold the mask-bank row count at
    1 — bank rows are legitimately shape and change only on calibration."""
    from repro.fl import shard_fleet
    from repro.fl.population import PopulationConfig, build_population
    cfg = PopulationConfig(n_clients=512, cohort_size=4, workload="synth",
                           backend="sharded_fleet", n_shards=2,
                           policy="none", n_partitions=8,
                           samples_per_partition=20, seed=0)
    sim = build_population(cfg)
    before = set(shard_fleet._SHARDED_CACHE)
    # Round 0 feeds host-resident init params; round 1+ params carry the
    # program's replicated NamedSharding, which legitimately costs one
    # extra compile. Steady state starts at round 1: from there the cache
    # must not grow, whatever cohort gets sampled.
    sim.run(2)
    new = [k for k in shard_fleet._SHARDED_CACHE if k not in before]
    if len(new) != 1:
        return [Violation("single-trace-population",
                          "ShardedFleetEngine program cache",
                          f"{len(new)} sharded programs built for one "
                          f"(model, mesh, S) (want 1)")]
    fn = shard_fleet._SHARDED_CACHE[new[0]]
    n0 = fn._cache_size()
    sim.run(2)                       # two more rounds, two more cohorts
    n = fn._cache_size()
    if not (n0 <= 2 and n == n0):
        return [Violation(
            "single-trace-population", "PopulationSim.run_round",
            f"sharded cohort program traced {n} times across 4 rounds "
            f"(want <= 2: init + steady state): a sampled id or shard "
            f"assignment is leaking into program structure")]
    return []


def check_population_no_host_sync() -> List[Violation]:
    """Device side of the population round loop, traced under x64: cohort
    sampling, the sharded cohort program, hierarchical combine, and the
    store scatter-update contain no f64 and no host callbacks. Straggler
    calibration (core/straggler.plan_from_store) is deliberately host-side
    numpy — it runs once per round on O(cohort) scalars OUTSIDE any traced
    program, and is therefore out of scope here by design."""
    from repro.core.aggregate import combine_partials
    from repro.fl.population import (ClientStore, _sample_cohort,
                                     _update_from_round)
    from repro.fl.shard_fleet import _sharded_cohort_fn
    from repro.launch.mesh import make_host_mesh
    from repro.models.small import SynthMLP

    out = []
    store = ClientStore.empty(64).register(
        np.arange(64), np.full(64, 10.0, np.float32),
        np.zeros(64, np.int32))
    out += _trace_violations(
        "population-no-host-sync", "ClientStore.sample_cohort",
        functools.partial(_sample_cohort, size=8), store.active,
        jax.random.PRNGKey(0))
    ids = jnp.arange(8, dtype=jnp.int32)
    out += _trace_violations(
        "population-no-host-sync", "ClientStore.update_from_round",
        _update_from_round, store, ids,
        jnp.full((8,), 10.0, jnp.float32), jnp.ones((8,), jnp.float32))

    # sharded cohort program + combine, S=2 shards of 2 clients on 1 device
    mesh = make_host_mesh(data=1)
    run = _sharded_cohort_fn(SynthMLP, mesh, 2, False)
    params = SynthMLP.init(jax.random.PRNGKey(0))
    bank = jax.tree.map(lambda p: p[None].astype(jnp.float32) * 0 + 1,
                        params)
    S, Cs, steps, bs = 2, 2, 1, 20
    cx = jnp.zeros((S, Cs, 2 * bs, 32), jnp.float32)
    order = jnp.zeros((S, Cs, steps, bs), jnp.int32)
    ys = jnp.zeros((S, Cs, steps, bs), jnp.int32)
    sw = jnp.ones((S, Cs, steps, bs), jnp.float32)
    mi = jnp.zeros((S, Cs), jnp.int32)
    lrs = jnp.full((S, Cs), 0.05, jnp.float32)
    w = jnp.full((S, Cs), float(bs), jnp.float32)
    out += _trace_violations(
        "population-no-host-sync", "sharded_cohort_program",
        functools.partial(run, n_steps=steps),
        params, bank, mi, cx, order, ys, sw, lrs, w)
    num = jax.tree.map(jnp.zeros_like, params)
    out += _trace_violations(
        "population-no-host-sync", "combine_partials",
        combine_partials, params, num, jnp.ones((1,), jnp.float32), bank)
    return out


def check_async_single_trace() -> List[Violation]:
    """The async server step compiles once at steady state: every dispatch
    group is capacity-padded to buffer_k clients (one cohort-program
    shape), every drained buffer is exactly buffer_k arrivals with a
    rebuilt mask bank of constant row count (policy='none' holds it at 1,
    as in the population check — bank rows are legitimately shape and move
    only on calibration), so neither the dispatch program nor
    `aggregate_buffered` may retrace per buffer, whatever arrival order
    the virtual clock produces. Round 0 feeds host-resident init params;
    steady state starts once params carry device sharding — budget <= 2
    traces for the init transition, then the caches must freeze."""
    from repro.core.aggregate import aggregate_buffered
    from repro.fl import fleet
    from repro.fl.async_rounds import AsyncConfig
    from repro.fl.population import PopulationConfig, build_population
    from repro.core.straggler import ArrivalModel

    cfg = PopulationConfig(
        n_clients=512, cohort_size=4, workload="synth", backend="async",
        policy="none", n_partitions=8, samples_per_partition=20,
        async_cfg=AsyncConfig(buffer_k=4, concurrency=8,
                              arrival=ArrivalModel(tail_sigma=0.5, seed=0)),
        seed=0)
    sim = build_population(cfg)
    before = set(fleet._COHORT_CACHE)
    agg0 = aggregate_buffered._cache_size()
    sim.run(2)
    new = [k for k in fleet._COHORT_CACHE if k not in before]
    progs = [fleet._COHORT_CACHE[k] for k in new] or [
        fleet._COHORT_CACHE[("SynthMLP", False, True)]]
    n0 = [p._cache_size() for p in progs]
    agg1 = aggregate_buffered._cache_size()
    sim.run(3)                  # more buffers, different arrival orders
    out = []
    n1 = [p._cache_size() for p in progs]
    agg2 = aggregate_buffered._cache_size()
    if n1 != n0:
        out.append(Violation(
            "single-trace-async", "async dispatch program",
            f"cohort program retraced at steady state ({n0} -> {n1}): a "
            f"dispatch-group shape is leaking arrival structure"))
    if not (agg1 - agg0 <= 2 and agg2 == agg1):
        out.append(Violation(
            "single-trace-async", "aggregate_buffered",
            f"buffer aggregation traced {agg1 - agg0} times in 2 rounds / "
            f"{agg2 - agg1} more in 3 rounds (want <= 2 then 0): buffer "
            f"composition is leaking into program shape"))
    return out


# ---------------------------------------------------------------------------
# dropped-dW-zero checks (NaN poison)

def _ffn_cases():
    """Unique (F, ffn_kind) over all configs/ FFN widths, incl. MoE expert
    width; kernel fleet models ride along with their gelu FFNs."""
    from repro.configs.base import all_configs
    cases = {}
    for arch, cfg in all_configs().items():
        for F in filter(None, (cfg.d_ff, cfg.moe_ff)):
            cases.setdefault((F, cfg.ffn_kind), arch)
    cases.setdefault((1024, "gelu"), "kernel_mlp")
    cases.setdefault((256, "gelu"), "kernel_attn")
    return cases


def check_dropped_dw_zero_ffn() -> List[Violation]:
    """For every distinct FFN width in the zoo: poison dropped 128-blocks
    with NaN, demand a finite forward and bitwise-zero dropped dW."""
    from repro.kernels.masked_ffn import BLOCK_NEURONS, masked_ffn
    from repro.models.layers import _KERNEL_ACT
    out = []
    d, M = 16, 8
    for (F, kind), arch in sorted(_ffn_cases().items()):
        where = f"masked_ffn[F={F}, {kind}] ({arch})"
        if F % BLOCK_NEURONS != 0:
            # kernel-ineligible width: the contract is a loud ValueError,
            # never a silent dense fallback (kernel_contracts re-checks)
            try:
                jax.eval_shape(functools.partial(masked_ffn, act="silu",
                                                 interpret=True),
                               jax.ShapeDtypeStruct((M, d), jnp.float32),
                               jax.ShapeDtypeStruct((d, F), jnp.float32),
                               jax.ShapeDtypeStruct((F, d), jnp.float32),
                               jax.ShapeDtypeStruct((F // BLOCK_NEURONS,),
                                                    jnp.float32))
            except ValueError:
                continue
            out.append(Violation("dw-zero-ffn", where,
                                 f"F={F} is not 128-aligned but masked_ffn "
                                 f"accepted it silently"))
            continue
        act, gated = _KERNEL_ACT[kind]
        nb = F // BLOCK_NEURONS
        block_mask = np.ones((nb,), np.float32)
        block_mask[1::2] = 0.0                       # drop every other block
        dropped = np.repeat(block_mask == 0, BLOCK_NEURONS)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(M, d).astype(np.float32))
        w_in = rng.randn(d, F).astype(np.float32)
        w_out = rng.randn(F, d).astype(np.float32)
        w_in[:, dropped] = np.nan                    # poison dropped tiles
        w_out[dropped, :] = np.nan
        w_gate = None
        if gated:
            w_gate = rng.randn(d, F).astype(np.float32)
            w_gate[:, dropped] = np.nan

        def loss(wi, wo, wg):
            return jnp.sum(masked_ffn(x, wi, wo, jnp.asarray(block_mask),
                                      wg, act=act, interpret=True))
        y = masked_ffn(x, jnp.asarray(w_in), jnp.asarray(w_out),
                       jnp.asarray(block_mask),
                       None if w_gate is None else jnp.asarray(w_gate),
                       act=act, interpret=True)
        if not np.isfinite(np.asarray(y)).all():
            out.append(Violation("dw-zero-ffn", where,
                                 "forward read a dropped (NaN-poisoned) "
                                 "weight tile"))
            continue
        grads = jax.grad(loss, argnums=(0, 1) + ((2,) if gated else ()))(
            jnp.asarray(w_in), jnp.asarray(w_out),
            None if w_gate is None else jnp.asarray(w_gate))
        named = [("dW_in", np.asarray(grads[0])[:, dropped]),
                 ("dW_out", np.asarray(grads[1])[dropped, :])]
        if gated:
            named.append(("dW_gate", np.asarray(grads[2])[:, dropped]))
        for gname, tile in named:
            if not (tile == 0.0).all():
                out.append(Violation(
                    "dw-zero-ffn", where,
                    f"{gname} of dropped blocks is not bitwise zero — the "
                    f"backward kernel touched a dropped tile"))
    return out


def check_dropped_dw_zero_attn() -> List[Violation]:
    """For every distinct head count in the zoo: poison dropped head slabs
    with NaN, demand a finite forward and bitwise-zero dropped dW."""
    from repro.kernels.masked_attn import masked_attention
    from repro.configs.base import all_configs
    heads = sorted({cfg.n_heads for cfg in all_configs().values()} | {4})
    out = []
    B, S, d, hd = 1, 4, 16, 8
    for H in heads:
        where = f"masked_attention[H={H}]"
        mask = np.ones((H,), np.float32)
        mask[1::2] = 0.0
        dropped = np.repeat(mask == 0, hd)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(B, S, d).astype(np.float32))
        ws = {}
        for name in ("wq", "wk", "wv"):
            w = rng.randn(d, H * hd).astype(np.float32)
            w[:, dropped] = np.nan
            ws[name] = jnp.asarray(w)
        wo = rng.randn(H * hd, d).astype(np.float32)
        wo[dropped, :] = np.nan
        ws["wo"] = jnp.asarray(wo)

        def loss(wq, wk, wv, wo_):
            return jnp.sum(masked_attention(x, wq, wk, wv, wo_,
                                            jnp.asarray(mask), n_heads=H,
                                            block_m=8, interpret=True))
        y = masked_attention(x, ws["wq"], ws["wk"], ws["wv"], ws["wo"],
                             jnp.asarray(mask), n_heads=H, block_m=8,
                             interpret=True)
        if not np.isfinite(np.asarray(y)).all():
            out.append(Violation("dw-zero-attn", where,
                                 "forward read a dropped (NaN-poisoned) "
                                 "head slab"))
            continue
        g = jax.grad(loss, argnums=(0, 1, 2, 3))(
            ws["wq"], ws["wk"], ws["wv"], ws["wo"])
        named = [("dWq", np.asarray(g[0])[:, dropped]),
                 ("dWk", np.asarray(g[1])[:, dropped]),
                 ("dWv", np.asarray(g[2])[:, dropped]),
                 ("dWo", np.asarray(g[3])[dropped, :])]
        for gname, tile in named:
            if not (tile == 0.0).all():
                out.append(Violation(
                    "dw-zero-attn", where,
                    f"{gname} of dropped heads is not bitwise zero — the "
                    f"backward kernel touched a dropped head slab"))
    return out


# ---------------------------------------------------------------------------
# registry / driver

CHECKS: Dict[str, Callable[[], List[Violation]]] = {
    "no-f64-zoo": check_zoo_train_no_f64,
    "no-f64-models": check_models_no_f64,
    "no-f64-optim": check_optim_no_f64,
    "single-trace-train": check_train_step_single_trace,
    "single-trace-fleet": check_fleet_single_trace,
    "single-trace-serve": check_serve_single_trace,
    "single-trace-population": check_population_single_trace,
    "single-trace-async": check_async_single_trace,
    "population-no-host-sync": check_population_no_host_sync,
    "dw-zero-ffn": check_dropped_dw_zero_ffn,
    "dw-zero-attn": check_dropped_dw_zero_attn,
}


def run_contracts(progress=None, only=None) -> List[Violation]:
    """Run trace-time contracts; `only` narrows to a list of CHECKS names
    (unknown names are a loud error, not an empty green run)."""
    checks = CHECKS
    if only:
        unknown = [n for n in only if n not in CHECKS]
        if unknown:
            raise KeyError(f"unknown contract(s) {unknown}; "
                           f"available: {sorted(CHECKS)}")
        checks = {n: CHECKS[n] for n in only}
    out = []
    for name, fn in checks.items():
        if progress:
            progress(name)
        try:
            out.extend(fn())
        except Exception as e:                       # noqa: BLE001
            out.append(Violation(name, fn.__name__,
                                 f"check crashed: {type(e).__name__}: {e}"))
    return out
