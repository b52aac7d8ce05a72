#!/usr/bin/env python3
"""Bring-up smoke on one TPU chip: serve OLMo-1B at full width through Gateway.

    python3 chip_smoke.py        # from the root of a checkout, on a host with a TPU

Deploys ``FunctionSpec(arch="olmo-1b", batch_size=2, prompt_len=128,
decode_steps=16, reduced=False)`` on ``Gateway(n_hosts=1, slots_per_host=1,
mode="cold", decode=True)``, so at most one cold executor and one decode
executor are resident on the chip. It then serves cold invokes through the
``unikernel`` and ``unikernel_stream`` drivers and mixed-budget
``invoke_decode`` requests, and compares the deployed Pallas programs with the
same computation under ``ops.impl_scope("ref")``, on the chip: the prefill
logits (head sub-program) and one paged decode step's logits. The weights are
random, made from the spec's seed.

Earlier lines print bring-up facts (deploy and compile seconds, each boot's
stage times, peak device memory, the flags checked, the logit deltas): they
are observations, not metrics. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed. The
script exits non-zero without it when JAX finds no TPU, when it runs outside a
checkout, or when any check fails. Everything runs in this one process: a chip
belongs to one process at a time.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List

os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

SRC = Path(__file__).resolve().parent / "src"

SPEC_ARGS = dict(arch="olmo-1b", batch_size=2, prompt_len=128, decode_steps=16,
                 reduced=False)
DECODE_BUDGETS = (16, 3, 9, 1, 12, 5)      # more requests than slots: rows join and leave
# Largest |pallas - ref| over the largest |ref| logit. Both sides run the bf16
# model on the same weights and differ only in the attention kernels'
# accumulation order, so the gap is a few bf16 roundings carried through 16
# layers; a wrong mask, head mapping or page lookup moves logits by O(1).
LOGIT_REL_TOL = 5e-2


def failures(facts: Dict) -> List[str]:
    """Every check the smoke makes, as messages for the ones that failed."""
    out = []
    if facts.get("platform") != "tpu":
        out.append(f"platform is {facts.get('platform')!r}, not 'tpu'")
    if facts.get("impl") != "pallas":
        out.append(f"ops resolves to {facts.get('impl')!r}, not 'pallas'")
    for flag in ("tpu_custom_call", "aot_verified", "split_serve",
                 "decode_aot_verified", "decode_pools_in_place"):
        if facts.get(flag) is not True:
            out.append(f"{flag} is {facts.get(flag)!r}")
    for name in ("prefill_rel_delta", "step_rel_delta"):
        delta = facts.get(name)
        if delta is None or not delta <= LOGIT_REL_TOL:
            out.append(f"{name} {delta!r} over tolerance {LOGIT_REL_TOL}")
    out.extend(facts.get("request_errors", ["no requests were served"]))
    return out


def _rel_delta(got, want) -> float:
    import numpy as np
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _print_boots(gw, label: str) -> None:
    for i, tl in enumerate(gw.recorder.timelines(label)):
        if tl.stage_s:                      # requests that paid a boot
            stages = {k: round(v, 4) for k, v in tl.stage_s.items()}
            print(f"boot {label}[{i}]: wall_s={tl.t_boot_wall:.4f} stages={stages}")


def _print_device_memory(phase: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"device memory {phase}: bytes_in_use={stats.get('bytes_in_use')} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)


def serve_and_compare(spec, work_dir: str) -> Dict:
    """Deploy ``spec``, serve it through every path, compare with ``ref``.

    Returns the facts ``failures`` checks. The kernels run as ``ops`` resolves
    them in the calling thread, so a caller may rehearse this on the CPU
    inside ``ops.impl_scope("interpret")``.
    """
    import jax
    import numpy as np

    from repro.core import Gateway
    from repro.core.deploy import make_step_fn
    from repro.kernels import ops

    facts: Dict = {"impl": ops._resolved()}
    errors: List[str] = []
    gw = Gateway(n_hosts=1, slots_per_host=1, mode="cold", decode=True,
                 work_dir=work_dir)
    try:
        t0 = time.perf_counter()
        dep = gw.deploy(spec)
        facts["deploy_s"] = time.perf_counter() - t0
        facts["build_s"] = dep.build_seconds
        extra = dep.image.manifest.extra
        facts["aot_verified"] = extra.get("aot_verified")
        facts["split_serve"] = extra.get("split_serve")
        bundle = gw.decoders[spec.name].bundle
        facts["decode_aot_verified"] = bundle.aot_verified
        facts["decode_pools_in_place"] = bundle.pools_in_place
        facts["tpu_custom_call"] = "tpu_custom_call" in dep.load_program().as_text()
        print(f"deploy: {spec.name} deploy_s={facts['deploy_s']:.2f} "
              f"build_s={facts['build_s']:.2f} "
              f"program_bytes={dep.image.manifest.program_bytes} "
              f"snapshot_bytes={dep.image.manifest.snapshot_bytes}", flush=True)
        _print_device_memory("after deploy")

        vocab = dep.model.cfg.vocab_size
        rng = np.random.default_rng(spec.seed)
        prompts = [rng.integers(0, vocab, (spec.batch_size, spec.prompt_len),
                                dtype=np.int32) for _ in range(2)]
        served = {}
        for driver in ("unikernel", "unikernel_stream"):
            label = f"smoke:{driver}"
            for i, tokens in enumerate(prompts):
                try:
                    out = np.asarray(gw.invoke(spec.name, tokens, driver=driver,
                                               label=label))
                except Exception as e:  # noqa: BLE001 - every failure is reported
                    errors.append(f"{driver} invoke {i}: {e!r}")
                    continue
                if out.shape != (spec.batch_size, spec.decode_steps) \
                        or out.min() < 0 or out.max() >= vocab:
                    errors.append(f"{driver} invoke {i}: bad output "
                                  f"{out.shape} {out.min()}..{out.max()}")
                served[driver, i] = out
            _print_boots(gw, label)
        for i in range(len(prompts)):
            a, b = served.get(("unikernel", i)), served.get(("unikernel_stream", i))
            if a is not None and b is not None and not np.array_equal(a, b):
                errors.append(f"invoke {i}: unikernel and unikernel_stream disagree")
        _print_device_memory("after cold invokes")

        budgets = [min(n, spec.decode_steps) for n in DECODE_BUDGETS]
        dprompts = rng.integers(0, vocab, (len(budgets), 1, spec.prompt_len),
                                dtype=np.int32)
        futs = [gw.invoke_decode_async(spec.name, dprompts[i], max_new=n,
                                       label="smoke:decode")
                for i, n in enumerate(budgets)]
        for i, (fut, n) in enumerate(zip(futs, budgets)):
            try:
                toks = np.asarray(fut.result(600))
            except Exception as e:  # noqa: BLE001 - every failure is reported
                errors.append(f"decode request {i}: {e!r}")
                continue
            if toks.shape != (n,) or toks.min() < 0 or toks.max() >= vocab:
                errors.append(f"decode request {i}: bad output {toks.shape}, "
                              f"wanted ({n},)")
        _print_boots(gw, "smoke:decode")
        print(f"decode: {json.dumps(gw.decode_summary(spec.name))}", flush=True)
        _print_device_memory("after decode")
    finally:
        gw.shutdown()
    _print_device_memory("after shutdown")
    gc.collect()
    _print_device_memory("after shutdown and gc")

    # ---- the deployed Pallas programs against the same math under ref
    model = dep.model
    params = model.init(jax.random.PRNGKey(spec.seed))
    capacity = spec.prompt_len + spec.decode_steps
    head = dep.cache.load_program(dep.head_program_key())
    _tok0, logits_p, _kv = head(params, prompts[0])
    step_args = _step_inputs(model, bundle, params, prompts[0])
    with ops.impl_scope("ref"):
        t0 = time.perf_counter()
        prefill_ref = jax.jit(lambda p, t: model.prefill(
            p, {"tokens": t}, capacity=capacity)[0]).lower(params, prompts[0]).compile()
        step_ref = jax.jit(make_step_fn(model)).lower(params, *step_args).compile()
        facts["ref_compile_s"] = time.perf_counter() - t0
    logits_r = prefill_ref(params, prompts[0])
    live = spec.batch_size                  # rows _step_inputs admitted
    # the ref first: the deployed step takes the pools donated and deletes them
    step_r = step_ref(params, *step_args)[0][:live]
    step_p = bundle.step(params, *step_args)[0][:live]
    for name, got, want in (("prefill", logits_p, logits_r),
                            ("step", step_p, step_r)):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        facts[f"{name}_rel_delta"] = _rel_delta(got, want)
        facts[f"{name}_argmax_agree"] = float(np.mean(
            np.argmax(got, -1) == np.argmax(want, -1)))
    _print_device_memory("after the comparison")
    facts["request_errors"] = errors
    return facts


def _step_inputs(model, bundle, params, prompt):
    """Admit each row of ``prompt`` into a fresh pool through the deployed
    admit program and return the step program's inputs (k_pages, v_pages,
    page_table, pos, token) for every admitted row's next token. The rows sit
    at depth ``prompt_len``, one token into a fresh page, so the step's page
    sweep ends on a ragged page."""
    import numpy as np

    pools = model.init_page_pool(bundle.n_pages, bundle.page_size)
    k, v = pools["k_pages"], pools["v_pages"]
    mp = bundle.max_pages
    table = np.zeros((bundle.slots, mp), np.int32)
    pos = np.zeros((bundle.slots,), np.int32)
    tok = np.zeros((bundle.slots, 1), np.int32)
    for r in range(prompt.shape[0]):
        table[r] = 1 + r * mp + np.arange(mp)
        logits, k, v = bundle.admit(params, prompt[r:r + 1], k, v, table[r])
        pos[r] = prompt.shape[1]
        tok[r, 0] = int(np.argmax(np.asarray(logits, np.float32)))
    return k, v, table, pos, tok


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run it from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.core.compile_cache import use_checkout_compile_cache
    print(f"compile cache: {use_checkout_compile_cache()}", flush=True)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1

    from repro.core.artifact import FunctionSpec
    spec = FunctionSpec(**SPEC_ARGS)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work_dir:
        try:
            facts = serve_and_compare(spec, work_dir)
        except Exception as e:  # noqa: BLE001 - reported, then exit non-zero
            import traceback
            traceback.print_exc()
            print(f"chip_smoke: failed: {e!r}", file=sys.stderr)
            return 1
    facts["platform"] = dev.platform
    stats = dev.memory_stats() or {}
    facts["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print(f"facts: {json.dumps(facts, default=str)}", flush=True)
    bad = failures(facts)
    if bad:
        for msg in bad:
            print(f"chip_smoke: FAIL {msg}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
