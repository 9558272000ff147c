#!/usr/bin/env python3
"""Chip smoke test: the serving path on one TPU, at published width.

Phases, in order; a failed check exits non-zero without the result line:

  device  the first JAX device must be a TPU. There is no CPU path.
  lm      two tenants of qwen2.5-3b at published width (bf16 weights made
          on the device from seeds 0 and 1) on the HydraPlatform (pool 2)
          that ``launch/serve.py`` builds; 8 requests through
          ContinuousBatcher (prompt 128, 32 new tokens, 8 slots, max_seq
          1024). Checks: every request returns 32 in-vocab tokens; both
          tenants share one compiled decode executable; no compile inside
          the serving loop after warm-up; the compiled prefill and decode
          contain Pallas kernels (``tpu_custom_call``); the serving path's
          prefill and first decode-step logits match a float32 reference
          (same bf16 weights, float32 activations, jnp reference kernels,
          highest matmul precision).
  fleet   the bundled Azure Functions sample (first 10 trace minutes at
          120x) replayed through the gateway: every submitted request
          served, no errors, no drops.

The last line of stdout is one JSON object naming the device. Run from the
root of a checkout:

  python3 chip_smoke.py
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
AZURE_SAMPLE = os.path.join(REPO, "benchmarks", "data", "azure_sample.csv")

# serve.py flags that define the LM phase at the chip's size
LM_ARGV = ["--archs", "qwen2.5-3b", "--tenants", "2", "--pool", "2",
           "--requests", "8", "--prompt-len", "128", "--max-new", "32",
           "--slots", "8", "--max-seq", "1024"]
FLEET_ARGV = ["--gateway", "--trace-file", AZURE_SAMPLE, "--max-minutes",
              "10", "--compress", "120", "--pool", "2"]
WARMUP_NEW = 2      # tokens per request in the warm-up round

# max |logit error| over max |reference logit|, prefill and first decode
# step. bf16 activations against the float32 reference measured 0.014 to
# 0.018 in the rehearsal at reduced width and full depth (36 layers, CPU,
# interpret-mode kernels); the bound leaves about 3x. The chip run prints
# its own error beside it.
LOGIT_TOL = 0.05

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache loads included) and
    persistent compile-cache hits, in any thread, while installed."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **kw):
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1

    def _on_event(self, event, **kw):
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def _serve_round(batchers, fids, prompts, max_new):
    """Submit every prompt (round-robin over tenants) and step all
    batchers until done. Returns each request's tokens, in order."""
    futs = [batchers[fids[i % len(fids)]].submit(p, max_new)
            for i, p in enumerate(prompts)]
    while any(b.active or b.pending for b in batchers.values()):
        for b in batchers.values():
            if b.active or b.pending:
                b.step()
    return [f.result() for f in futs]


def _logits_error(cfg, params, prompt):
    """(max |serving - reference| logit error, max |reference logit|)
    over the prefill's last-token logits and the first decode step's.
    Both paths decode the same next token: the serving path's argmax."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models.programs import ModelProgram

    tokens = jnp.asarray(prompt, jnp.int32)[None]

    def two_steps(prog, next_tok=None):
        logits0, cache = jax.jit(prog.prefill)(params, {"tokens": tokens})
        # room for the decoded token: the prefill cache holds the prompt
        pad = ((0, 0), (0, 0), (0, 128), (0, 0), (0, 0))
        cache = {k: v if k == "length" else jnp.pad(v, pad)
                 for k, v in cache.items()}
        if next_tok is None:
            next_tok = jnp.argmax(logits0, axis=-1)[:, None].astype(
                jnp.int32)
        logits1, _ = jax.jit(prog.decode_step)(params, cache,
                                                {"tokens": next_tok})
        return (logits0.astype(jnp.float32), logits1.astype(jnp.float32),
                next_tok)

    s0, s1, tok = two_steps(ModelProgram(cfg, remat=False))
    ref_prog = ModelProgram(dataclasses.replace(cfg, dtype="float32"),
                            remat=False)
    mode = ops.kernel_mode()
    ops.set_kernel_mode("ref")
    try:
        with jax.default_matmul_precision("highest"):
            r0, r1, _ = two_steps(ref_prog, tok)
    finally:
        ops.set_kernel_mode(mode)
    err = max(float(jnp.max(jnp.abs(s0 - r0))),
              float(jnp.max(jnp.abs(s1 - r1))))
    scale = max(float(jnp.max(jnp.abs(r0))), float(jnp.max(jnp.abs(r1))))
    return err, scale


def lm_phase(argv) -> dict:
    """Serve LM requests through the platform that ``serve.py``'s flags
    ``argv`` select; raise SmokeFailure on any failed check. Returns what
    the caller prints and the compiled HLO of prefill and decode."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import HydraPlatform, LMSpec
    from repro.core.arena import tree_bytes
    from repro.core.scheduler import ContinuousBatcher
    from repro.launch.serve import build_parser, build_target, make_params

    args = build_parser().parse_args(argv)
    cfg = get_config(args.archs)
    if args.reduced:
        cfg = cfg.reduced()
    out = {"arch": cfg.name}
    platform = build_target(args)
    check(isinstance(platform, HydraPlatform),
          f"serve flags {argv} did not build a HydraPlatform")
    try:
        t0 = time.perf_counter()
        params = [jax.block_until_ready(make_params(cfg, seed=t))
                  for t in range(args.tenants)]
        out["init_s"] = time.perf_counter() - t0
        out["weight_bytes_per_tenant"] = tree_bytes(params[0])

        fids, out["register_s"] = [], []
        for t, p in enumerate(params):
            fid = f"tenant{t}/{args.archs}"
            t0 = time.perf_counter()
            platform.register_function(
                fid, LMSpec(cfg=cfg, params=p, max_seq=args.max_seq,
                            slots=args.slots),
                tenant=f"tenant{t}", eager=True)
            out["register_s"].append(time.perf_counter() - t0)
            fids.append(fid)
        funcs = [platform.runtime_for(f).registry.get(f) for f in fids]

        rng = np.random.default_rng(args.seed)
        prompts = [rng.integers(0, cfg.vocab_size, args.prompt_len).tolist()
                   for _ in range(args.requests)]
        batchers = {f: ContinuousBatcher(platform.runtime_for(f), f)
                    for f in fids}
        try:
            t0 = time.perf_counter()
            _serve_round(batchers, fids, prompts, WARMUP_NEW)
            out["warmup_s"] = time.perf_counter() - t0
            exe_before = platform.exe_cache.stats()
            with CompileCounter() as cc:
                t0 = time.perf_counter()
                results = _serve_round(batchers, fids, prompts, args.max_new)
                out["serve_s"] = time.perf_counter() - t0
        finally:
            for b in batchers.values():
                b.close()
        exe = platform.exe_cache.stats()
        out["exe_cache"] = exe
        out["loop_compiles"] = cc.compiles
        out["tokens"] = sum(len(r) for r in results)
        out["peak_bytes_after_serving"] = _peak_bytes()

        for i, r in enumerate(results):
            check(len(r) == args.max_new,
                  f"request {i} returned {len(r)} tokens, not "
                  f"{args.max_new}")
            check(all(0 <= t < cfg.vocab_size for t in r),
                  f"request {i} returned a token outside the vocabulary")
        check(all(f.entry["decode"] is funcs[0].entry["decode"]
                  for f in funcs),
              "tenants do not share one decode executable")
        check(exe["hits"] >= len(funcs) - 1,
              f"exe cache shows no sharing: {exe}")
        check(cc.compiles == 0 and exe["compiles"] == exe_before["compiles"],
              f"{cc.compiles} compile(s) inside the serving loop")
        rt = platform.runtime_for(fids[0])
        out["hlo"] = {
            "prefill": rt._lm_prefill_exe(funcs[0], args.prompt_len)
            .as_text(),
            "decode": funcs[0].entry["decode"].as_text()}
        ref_params = params[0]
        del funcs, params, batchers, rt
    finally:
        platform.shutdown()
    del platform
    gc.collect()

    err, scale = _logits_error(cfg, ref_params, prompts[0])
    out["logit_err"], out["logit_scale"] = err, scale
    check(err <= LOGIT_TOL * scale,
          f"logits differ from the float32 reference: max |err| {err} > "
          f"{LOGIT_TOL} x max |ref| {scale}")
    return out


def fleet_phase(argv) -> dict:
    """Replay a trace through the gateway with ``serve.py``'s flags
    ``argv``; raise SmokeFailure unless every submitted request was
    served without errors or drops."""
    from repro.launch.serve import build_parser, run_gateway

    summary = run_gateway(build_parser().parse_args(argv))
    check(summary["submitted"] > 0, "the gateway submitted no requests")
    check(not summary["errors"],
          f"gateway errors: {summary['errors'][:3]}")
    check(summary["dropped"] == 0, f"{summary['dropped']} requests dropped")
    check(summary["requests"] == summary["submitted"],
          f"served {summary['requests']} of {summary['submitted']}")
    return summary


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: the first JAX device is {dev.platform!r}; "
              f"this smoke test runs only on a TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.core.executable_cache import configure_compile_cache
    except ImportError as e:
        print(f"[smoke] FAIL: run from the root of a checkout ({e})",
              file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x{len(jax.devices())}")
    cache_dir = configure_compile_cache()
    log(f"compile cache: {cache_dir}")

    try:
        with CompileCounter() as whole:
            t0 = time.perf_counter()
            lm = lm_phase(LM_ARGV)
            for name, text in lm.pop("hlo").items():
                check("tpu_custom_call" in text,
                      f"compiled {name} has no Pallas kernel "
                      f"(tpu_custom_call)")
            log(f"lm: {lm['arch']}, weights "
                f"{lm['weight_bytes_per_tenant']} bytes per tenant, init "
                f"{lm['init_s']:.3f}s, register "
                f"{[round(s, 3) for s in lm['register_s']]}s, warm-up "
                f"{lm['warmup_s']:.3f}s")
            log(f"lm: exe cache {lm['exe_cache']}")
            log(f"lm: {lm['tokens']} tokens in {lm['serve_s']:.3f}s "
                f"({lm['tokens'] / lm['serve_s']:.1f} tok/s, a smoke "
                f"number), {lm['loop_compiles']} compiles in the loop, "
                f"peak HBM {lm['peak_bytes_after_serving']} bytes")
            log(f"lm: max logit error {lm['logit_err']} "
                f"(max |ref| {lm['logit_scale']}, bound "
                f"{LOGIT_TOL} x max |ref|)")
            fleet = fleet_phase(FLEET_ARGV)
            log(f"fleet: served {fleet['requests']}/{fleet['submitted']}, "
                f"dropped {fleet['dropped']}, errors {len(fleet['errors'])}")
            log(f"peak HBM {_peak_bytes()} bytes; {whole.compiles} "
                f"compiles, {whole.cache_hits} persistent-cache hits in "
                f"{time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
