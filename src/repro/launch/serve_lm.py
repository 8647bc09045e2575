"""Guarded transformer LM serving driver (benchmark mode).

The LM analog of ``repro.launch.serve_gcn``: prefill + greedy decode
through :class:`~repro.engine.lm.LMEngine`, i.e. under the full ABFT
ladder — every linear chain in the step is a checked op (QKV /
attention-out / MLP split corners, attention's fused carried-column
chain), per-op verdicts are keyed ``op:<id>`` for the guard, a flagged
step retries, a persistent flag refolds the working params from the
pristine master and replays, and recurring sites mark the backend
suspect.

The driver also makes the two acceptance claims executable:

* **clean overhead is checks-only** — on a clean run the guarded logits
  are verified bit-identical to the unguarded (``mode="none"``) forward,
  prefill and every decode step;
* **the ladder repairs** — ``--inject-at`` fires the attention
  accumulator fault operand on one step and the driver verifies it was
  flagged, repaired, and the final tokens match the clean reference.

    PYTHONPATH=src python -m repro.launch.serve_lm --new 16 \
        --inject-at 3 --json BENCH_lm_serve.json

The JSON payload carries the standard ``interpret``/``authoritative``
stamps (interpret-mode kernels make detection results functional but
timings non-authoritative, same convention as every other benchmark).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.abft import ABFTConfig
from repro.engine.lm import LMEngine
from repro.kernels.runtime import resolve_interpret, use_compile_cache
from repro.models.transformer import model_decode, model_prefill


def _clean_reference(engine: LMEngine, tokens, n_new: int):
    """The unguarded ``mode='none'`` trajectory on the MASTER params:
    per-step logits + greedy tokens, the bit-identity baseline."""
    off = ABFTConfig(mode="none")
    cfg, params = engine.cfg, engine._master
    prefill = jax.jit(lambda p, b: model_prefill(p, cfg, b, off,
                                                 engine.cache_len))
    decode = jax.jit(lambda p, s, t, i: model_decode(p, cfg, s, t, i, off))
    logits, states, _ = prefill(params, {"tokens": tokens})
    ref_logits, ref_tokens = [np.asarray(logits)], []
    t0 = tokens.shape[1]
    for i in range(n_new):
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        ref_tokens.append(np.asarray(nxt))  # abftlint: sync-ok (reference trace)
        logits, states, _ = decode(params, states, nxt,
                                   jnp.asarray(t0 + i, jnp.int32))
        ref_logits.append(np.asarray(logits))  # abftlint: sync-ok (reference trace)
    return ref_logits, ref_tokens


def main(argv: Optional[Sequence[str]] = None) -> dict:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--new", type=int, default=16,
                    help="greedy decode steps after the prefill")
    ap.add_argument("--mode", default="fused",
                    choices=["none", "split", "fused"])
    ap.add_argument("--threshold", type=float, default=1e-3)
    ap.add_argument("--inject-at", type=int, default=None,
                    help="fire the attention-accumulator fault operand on "
                         "this decode step (-1 = during prefill) and "
                         "verify the guard detects + repairs it")
    ap.add_argument("--inject-delta", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="BENCH_lm_serve.json",
                    help="write the machine-readable payload here "
                         "('' disables)")
    ap.add_argument("--assert-clean", action="store_true",
                    help="exit non-zero unless guarded logits are "
                         "bit-identical to the unguarded forward (and the "
                         "injected fault, if any, was detected+repaired)")
    args = ap.parse_args(argv)

    interp = resolve_interpret(None)
    cfg = smoke_config(get_config(args.arch))
    abft = ABFTConfig(mode=args.mode, threshold=args.threshold,
                      relative=True)
    cache_len = args.prompt + args.new
    engine = LMEngine.init(cfg, abft, jax.random.PRNGKey(args.seed),
                           cache_len=cache_len)
    rng = np.random.default_rng(args.seed)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size,
                                      size=(args.batch, args.prompt)),
                         jnp.int32)
    print(f"=== serve_lm: {cfg.name} batch={args.batch} "
          f"prompt={args.prompt} new={args.new} abft={args.mode} "
          f"({jax.default_backend()}) ===")

    # the bit-identity baseline: unguarded mode="none" on the master
    ref_logits, ref_tokens = _clean_reference(engine, tokens, args.new)

    # clean guarded pass (also the compile warmup for the timed phase)
    logits, states, _m = engine.prefill(tokens)
    identical = np.array_equal(np.asarray(logits), ref_logits[0])
    for i in range(args.new):
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        identical &= np.array_equal(np.asarray(nxt), ref_tokens[i])  # abftlint: sync-ok
        logits, states, _m = engine.decode(states, nxt, args.prompt + i)
        identical &= np.array_equal(np.asarray(logits), ref_logits[i + 1])  # abftlint: sync-ok
    clean_flags = engine.guard.flags
    print(f"clean guarded trajectory bit-identical to unguarded: "
          f"{bool(identical)} (flags={clean_flags})")

    # timed sustained phase (shapes warm — measures the guarded steps)
    t0 = time.perf_counter()
    logits, states, _m = engine.prefill(tokens)
    t_prefill = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(args.new):
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        logits, states, _m = engine.decode(states, nxt, args.prompt + i)
    jax.block_until_ready(logits)  # abftlint: sync-ok (benchmark timing barrier)
    t_decode = time.perf_counter() - t0
    ms_step = t_decode / max(args.new, 1) * 1e3
    print(f"prefill {args.batch}x{args.prompt}: {t_prefill*1e3:.0f} ms; "
          f"decoded {args.new} steps in {t_decode:.2f}s "
          f"({ms_step:.1f} ms/step)")

    # fault demo: one transient accumulator upset through the full ladder
    fault = None
    if args.inject_at is not None:
        flags0, retries0 = engine.guard.flags, engine.guard.retries
        toks, _stats = engine.generate(tokens, args.new,
                                       inject_at=args.inject_at,
                                       inject_delta=args.inject_delta)
        detected = engine.guard.flags > flags0
        repaired = np.array_equal(
            np.asarray(toks),
            np.concatenate(ref_tokens, axis=1)[:, :args.new])
        fault = {"inject_at": args.inject_at,
                 "inject_delta": args.inject_delta,
                 "detected": bool(detected),
                 "repaired_bitwise": bool(repaired),
                 "retries": engine.guard.retries - retries0}
        print(f"fault demo: inject_at={args.inject_at} "
              f"delta={args.inject_delta} detected={fault['detected']} "
              f"repaired_bitwise={fault['repaired_bitwise']}")

    stats = engine.stats()
    print(f"guard: steps={stats['steps']} flags={stats['flags']} "
          f"retries={stats['retries']} restores={stats['restores']} "
          f"flag_rate={stats['flag_rate']:.4f}")
    if interp:
        print("WARNING: interpret-mode kernels (no real accelerator) — "
              "detection results are functional, timings would NOT be "
              "authoritative")

    payload = {
        "benchmark": "lm_serve",
        "backend": jax.default_backend(),
        "interpret": bool(interp),
        "authoritative": not bool(interp),
        "config": {"arch": args.arch, "model": cfg.name,
                   "batch": args.batch, "prompt": args.prompt,
                   "new": args.new, "mode": args.mode,
                   "threshold": args.threshold, "seed": args.seed},
        "clean": {"bitwise_identical": bool(identical),
                  "flags": int(clean_flags)},
        "timings": {"prefill_ms": t_prefill * 1e3,
                    "decode_ms_per_step": ms_step},
        "fault": fault,
        "guard": stats,
    }
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json}")

    if args.assert_clean:
        failures = []
        if not identical:
            failures.append("guarded logits diverged from the unguarded "
                            "forward on a clean run")
        if clean_flags:
            failures.append(f"clean run flagged {clean_flags} steps")
        if fault is not None and not (fault["detected"]
                                      and fault["repaired_bitwise"]):
            failures.append(f"injected fault not repaired: {fault}")
        if failures:
            for f in failures:
                print(f"FAIL: {f}", file=sys.stderr)
            sys.exit(1)
        print("gates: clean bit-identity" +
              (", fault detected+repaired" if fault else "") + " — ok")
    return payload


if __name__ == "__main__":
    main()
