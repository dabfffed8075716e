"""Serving driver: continuous-batching engine over personalized sub-models.

Default path: launch/serving.ServeEngine — one compiled decode chunk serves
a queue of requests with mixed dropout rates, prompt lengths, and generation
lengths (see that module's docstring). ``--baseline`` instead runs the
original synchronous path (one Python-loop token at a time, whole batch in
lockstep) — kept as the engine's reference and the encoder-decoder
fallback. On CPU runs the smoke config; on a real mesh the steps inherit the
launch shardings.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import sharding as shlib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serving import ServeEngine, ServeRequest, rate_masks
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import model as model_lib


def serve(cfg, batch=2, prompt_len=16, gen_len=16, mla_absorb=False,
          seed=0, greedy=True):
    key = jax.random.PRNGKey(seed)
    params = model_lib.init_params(cfg, key)
    rng = np.random.RandomState(seed)
    toks = jnp.asarray(rng.randint(0, min(cfg.vocab_size, 256),
                                   (batch, prompt_len), dtype=np.int32))
    batch_in = {"tokens": toks}
    if cfg.is_encdec:
        batch_in["frames"] = jnp.asarray(
            rng.randn(batch, prompt_len, cfg.d_model).astype(np.float32)
            * 0.1).astype(cfg.dtype)

    prefill = jax.jit(make_prefill_step(cfg, cache_len=prompt_len + gen_len),
                      donate_argnums=shlib.donate_args())
    step = jax.jit(make_serve_step(cfg, mla_absorb=mla_absorb),
                   donate_argnums=shlib.donate_args(1))

    t0 = time.perf_counter()
    logits, caches = prefill(params, batch_in)
    t_prefill = time.perf_counter() - t0

    out = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.perf_counter()
    for t in range(gen_len):
        pos = jnp.full((batch,), prompt_len + t, jnp.int32)
        logits, caches = step(params, caches, tok, pos)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(np.asarray(tok[:, 0]))
    t_decode = time.perf_counter() - t0
    gen = np.stack(out, 1)
    return gen, {"prefill_s": t_prefill, "decode_s": t_decode,
                 "tok_per_s": batch * gen_len / max(t_decode, 1e-9)}


def serve_engine(cfg, batch=4, prompt_len=16, gen_len=16, n_requests=None,
                 rates=(1.0, 0.5), mla_absorb=False, seed=0, kernels=None):
    """Queue n_requests with cycling dropout rates and ragged prompt/gen
    lengths through one ServeEngine; returns (results, summary)."""
    params = model_lib.init_params(cfg, jax.random.PRNGKey(seed))
    eng = ServeEngine(cfg, params, batch_size=batch,
                      max_prompt_len=prompt_len, max_gen_len=gen_len,
                      mla_absorb=mla_absorb, kernels=kernels)
    rng = np.random.RandomState(seed)
    mask_of = {r: (None if r >= 1.0 else rate_masks(cfg, r, seed=seed))
               for r in rates}
    n_requests = n_requests or 2 * batch
    for i in range(n_requests):
        L = prompt_len if eng.recurrent else int(
            rng.randint(max(1, prompt_len // 2), prompt_len + 1))
        toks = rng.randint(0, min(cfg.vocab_size, 256), (L,), dtype=np.int32)
        g = int(rng.randint(max(1, gen_len // 2), gen_len + 1))
        eng.submit(ServeRequest(toks, gen_len=g, masks=mask_of[
            rates[i % len(rates)]]))
    results = eng.run()
    return results, eng.summary()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--rates", default="1.0,0.5",
                    help="comma-separated sub-model sizes cycled across "
                    "requests (1.0 = full model)")
    ap.add_argument("--baseline", action="store_true",
                    help="synchronous Python-loop decode (no engine)")
    ap.add_argument("--kernels", action="store_true",
                    help="trace the Pallas serving kernels (compiled on a "
                    "TPU, interpreted elsewhere)")
    ap.add_argument("--mla-absorb", action="store_true")
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.smoke()
    with shlib.mesh_context(None):
        if args.baseline:
            gen, stats = serve(cfg, args.batch, args.prompt_len,
                               args.gen_len, mla_absorb=args.mla_absorb)
            print("generated tokens:\n", gen)
            print({k: round(v, 3) for k, v in stats.items()})
            return
        rates = tuple(float(r) for r in args.rates.split(","))
        kern = {"ffn": True, "attn": True} if args.kernels else None
        results, summary = serve_engine(
            cfg, args.batch, args.prompt_len, args.gen_len,
            n_requests=args.n_requests, rates=rates,
            mla_absorb=args.mla_absorb, kernels=kern)
        for rid in sorted(results):
            print(f"request {rid}: {results[rid].tolist()}")
        print({k: (round(v, 3) if isinstance(v, float) else v)
               for k, v in summary.items()})


if __name__ == "__main__":
    main()
