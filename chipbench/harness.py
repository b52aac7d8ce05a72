"""One run of one cell: set-up, the measured window, the readings, the check.

The system under test is the platform's front door as a user calls a
deployed model: ``Gateway(n_hosts=1, slots_per_host=1, mode="cold",
decode=DecodeConfig(...)).invoke_decode_async``, with the ``unikernel`` boot
from the chunk store and cooling to zero after ``cool_after_s``. The harness
takes from it only that entry, its ``Timeline`` stamps, the decode tier's
counters and the names of its programs in the device trace.

Set-up (``setup_s``, from process start to the window's start): import,
deploy (its compiles come from JAX's persistent cache after a cell's first
run), warm-up requests that boot the tier and run the admit and step
programs, then either a long request that keeps the tier resident into the
lead-in (steady traffic) or a wait until the tier has cooled to zero
(``cooled_start``). The lead-in traffic, due before the window opens, counts
as set-up too. Nothing compiles inside the window: both programs are
compiled at deploy and run in the warm-up.

The window: an open loop sends each request at its due time on the
program's clock (``repro.core.metrics.now``), whatever has finished; a
sampler thread reads device memory every 100 ms. Requests due in the window
are waited for until a minute past its close.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import threading
import time
from concurrent.futures import Future
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import traffic as traffic_mod

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "chipbench"
WORK_DIR = ROOT / ".chipbench_work"         # the run's gateway files and trace; removed
HBM_PERIOD_S = 0.1
LATE_WAIT_S = 60.0                          # how long past the close a due request may take
KEEPALIVE_MAX_NEW = 256
KNEE_TTFT_FACTOR = 2.0                      # the knee sweep's TTFT rule


# ------------------------------------------------------------------ the cell

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict


def load_cell(workload: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file.name}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    tr = json.loads((BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(workload, int(w["chips"]), w["config"], cfg, w["traffic"], tr)


def metric_entries(workload: str, trace: bool,
                   bench_file: Path = ROOT / "BENCHMARK.json") -> List[Dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer ones."""
    bench = json.loads(bench_file.read_text())
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load_reader(name: str) -> Callable:
    """``chipbench/metrics/<name>.py``'s ``read(run)``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------- program glue

ARCH_KEYS = {            # configuration-file key -> repro ArchConfig field
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "head_dim": "head_dim", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype",
}
NORMS = {"layernorm_nonparametric": "layernorm_np", "layernorm": "layernorm"}
ACTS = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}


def check_arch(cfg: Dict) -> None:
    """Raise where the program's registered architecture is not the file's."""
    from repro.configs import get_config
    arch = get_config(cfg["arch"])
    if cfg["function"]["reduced"]:
        arch = arch.reduced()
    want = {f: cfg[k] for k, f in ARCH_KEYS.items()}
    want.update(norm=NORMS[cfg["norm"]], act=ACTS[cfg["hidden_act"]],
                qkv_bias=cfg["use_bias"], mlp_bias=cfg["use_bias"])
    got = {f: getattr(arch, f) for f in want}
    if got != want:
        diff = {f: (got[f], want[f]) for f in want if got[f] != want[f]}
        raise ValueError(f"{cfg['arch']} as the program builds it differs from "
                         f"the configuration file (program, file): {diff}")


def weight_seed(seed: int) -> int:
    """The seed the weights are made from (PRNGKey takes 32 bits)."""
    return seed % (2 ** 31)


@dataclasses.dataclass
class Served:
    req: traffic_mod.Request
    due: float                       # absolute, program clock
    submitted: float
    future: Future
    tokens: Optional[np.ndarray] = None
    error: Optional[str] = None
    timeline: Optional[object] = None


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    seconds: float
    t0: float                        # the window's start, program clock
    setup_s: float
    window: List[Served]             # requests due in the window
    lead_in: List[Served]
    hbm_bytes: List[int]
    counters: Dict[str, Dict[str, float]]   # "start"/"end" -> decode tier counters
    slots: int
    device_kind: str
    trace: Optional[object] = None          # trace_reduce.DeviceSummary
    trace_counters: Dict[str, Dict[str, float]] = dataclasses.field(default_factory=dict)
    row_lengths: List[int] = dataclasses.field(default_factory=list)

    def per_step(self, count: Callable) -> Optional[float]:
        """``count(cfg, lengths)`` (``chipbench.counts``) for the mean step of
        the traced window: its mean over the live rows' lengths there, times
        the rows a step held (the decode tier's counters at the trace's two
        ends)."""
        a, b = self.trace_counters.get("start"), self.trace_counters.get("end")
        if not a or not b or b["steps"] <= a["steps"] or not self.row_lengths:
            return None
        rows_per_step = (b["step_rows"] - a["step_rows"]) / (b["steps"] - a["steps"])
        return count(self.cell.config, self.row_lengths) / len(self.row_lengths) * rows_per_step


def _counters(decoder) -> Dict[str, float]:
    return {k: float(getattr(decoder, k))
            for k in ("steps", "step_rows", "admits", "boots", "cooldowns")}


class _Sampler(threading.Thread):
    """Reads device memory every ``HBM_PERIOD_S`` through the window (where
    ``dev`` is given) and the decode tier's counters at its two ends."""

    def __init__(self, dev, decoder, t0: float, t1: float, now) -> None:
        super().__init__(name="chipbench-sampler", daemon=True)
        self.dev, self.decoder, self.t0, self.t1, self.now = dev, decoder, t0, t1, now
        self.hbm: List[int] = []
        self.counters: Dict[str, Dict[str, float]] = {}

    def _sleep_until(self, t: float) -> None:
        d = t - self.now()
        if d > 0:
            time.sleep(d)

    def run(self) -> None:
        self._sleep_until(self.t0)
        self.counters["start"] = _counters(self.decoder)
        t = self.t0
        while t < self.t1:
            stats = (self.dev.memory_stats() if self.dev is not None else None) or {}
            if "bytes_in_use" in stats:
                self.hbm.append(int(stats["bytes_in_use"]))
            t += HBM_PERIOD_S
            self._sleep_until(t)
        self.counters["end"] = _counters(self.decoder)


class _Tracer(threading.Thread):
    """Starts the profiler at ``t_start`` and stops it ``length`` later,
    reading the decode tier's counters at both ends."""

    def __init__(self, trace_dir: Path, t_start: float, length: float, now,
                 decoder) -> None:
        super().__init__(name="chipbench-tracer", daemon=True)
        self.trace_dir, self.t_start, self.length, self.now = trace_dir, t_start, length, now
        self.decoder = decoder
        self.span = (0.0, 0.0)
        self.counters: Dict[str, Dict[str, float]] = {}
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        import jax
        try:
            d = self.t_start - self.now()
            if d > 0:
                time.sleep(d)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # Python calls unrecorded: the host runs as untraced
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
            a = self.now()
            self.counters["start"] = _counters(self.decoder)
            time.sleep(self.length)
            self.counters["end"] = _counters(self.decoder)
            b = self.now()
            jax.profiler.stop_trace()
            self.span = (a, b)
        except BaseException as e:  # noqa: BLE001 - re-raised by the harness
            self.error = e


def row_lengths(served: List[Served], prompt_len: int, a: float, b: float) -> List[int]:
    """The context length of every live row of every step in ``[a, b]``,
    worked out from the requests' own stamps: a request that got ``n``
    tokens made its first at ``t_ttfr`` (the admit) and the other ``n - 1``
    in as many steps up to ``t_done``, taken as evenly spaced; its ``j``-th
    step attends over ``prompt_len + j`` keys, the new one included."""
    out: List[int] = []
    for s in served:
        tl = s.timeline
        if tl is None or s.tokens is None or len(s.tokens) < 2:
            continue
        j = np.arange(1, len(s.tokens))
        t = tl.t_ttfr + j * (tl.t_done - tl.t_ttfr) / (len(s.tokens) - 1)
        out.extend((prompt_len + j[(t >= a) & (t <= b)]).tolist())
    return out


# --------------------------------------------------------------- the run

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_process: float,
             control: bool = False, sweep_rates: Optional[List[float]] = None,
             fault: Optional[Callable] = None) -> Dict:
    """Run ``cell`` once. Returns the readings, the check and the device.

    ``fault``, for the benchmark's own tests only, is called with the decode
    scheduler before the window and may break the timed path.
    """
    import jax

    from repro.core.decode import DecodeConfig
    from repro.core.gateway import Gateway
    from repro.core.artifact import FunctionSpec
    from repro.core.metrics import now

    cfg, tr = cell.config, cell.traffic
    check_arch(cfg)
    fn, dc = cfg["function"], cfg["decode"]
    spec = FunctionSpec(arch=cfg["arch"], batch_size=fn["batch_size"],
                        prompt_len=fn["prompt_len"], decode_steps=fn["decode_steps"],
                        reduced=fn["reduced"], seed=weight_seed(seed))
    dcfg = DecodeConfig(slots=dc["slots"], page_size=dc["page_size"],
                        cool_after_s=dc["cool_after_s"])
    dev = jax.devices()[0]
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    WORK_DIR.mkdir(parents=True)
    gw = Gateway(n_hosts=1, slots_per_host=1, mode="cold", decode=dcfg,
                 work_dir=str(WORK_DIR / "gateway"))
    try:
        gw.deploy(spec)
        decoder = gw.decoders[spec.name]
        vocab, P = cfg["vocab_size"], fn["prompt_len"]
        warm = traffic_mod.prompt(seed, traffic_mod.WARMUP_INDEX, P, vocab)
        for f in [gw.invoke_decode_async(spec.name, warm, max_new=tr["warmup_max_new"])
                  for _ in range(tr["warmup_requests"])]:
            f.result(900)
        if sweep_rates is not None:
            return _sweep(gw, spec, decoder, cell, seed, seconds, sweep_rates, now)
        sched = traffic_mod.schedule(tr, seed, seconds)
        prompts = {r.index: traffic_mod.prompt(seed, r.index, P, vocab) for r in sched}
        if fault is not None:
            fault(decoder)
        if tr.get("cooled_start"):
            t_wait = now()
            while decoder.cooldowns < 1 or decoder.cooldowns != decoder.boots:
                if now() - t_wait > 60:
                    raise RuntimeError("the decode tier did not cool within 60 s")
                time.sleep(0.05)
            lead = 0.0
            resident = []
        else:
            lead = float(tr.get("lead_in_s", 0.0))
            keep = traffic_mod.Request(-lead, min(KEEPALIVE_MAX_NEW, fn["decode_steps"]),
                                       traffic_mod.WARMUP_INDEX)
            t_keep = now()
            resident = [Served(keep, t_keep, t_keep, gw.invoke_decode_async(
                spec.name, warm, max_new=keep.max_new,
                label=f"chipbench:{keep.index}"))]
        t0 = now() + lead + 0.05
        sampler = _Sampler(dev, decoder, t0, t0 + seconds, now)
        sampler.start()
        tracer = None
        if trace:
            start, length = tr["trace_window_s"]
            length = min(length, seconds)
            start = min(start, seconds - length)     # a shorter run traces its end
            tracer = _Tracer(WORK_DIR / "trace", t0 + start, length, now, decoder)
            tracer.start()
        setup_s = t0 - t_process          # both on perf_counter
        served = _drive(gw, spec.name, sched, prompts, t0, now)
        sampler.join()
        if tracer is not None:
            tracer.join()
            if tracer.error is not None:
                raise tracer.error
        _settle(resident + served, gw, t0 + seconds + LATE_WAIT_S)
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        run = Run(cell=cell, seconds=seconds, t0=t0, setup_s=setup_s,
                  window=[s for s in served if s.req.due_s >= 0],
                  lead_in=resident + [s for s in served if s.req.due_s < 0],
                  hbm_bytes=sampler.hbm, counters=sampler.counters,
                  slots=decoder.slots, device_kind=dev.device_kind)
        if trace:
            _read_trace(run, tracer)
    finally:
        gw.shutdown()
        del gw
        gc.collect()
    result = {"run": run, "peak": peak, "prompts": prompts}
    result["check"] = _check(run, prompts, cfg, seed, control)
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    return result


def _drive(gw, name: str, sched, prompts, t0: float, now,
           label: str = "chipbench") -> List[Served]:
    """The open loop: each request at its due time, whatever has finished."""
    out = []
    for r in sched:
        due = t0 + r.due_s
        d = due - now()
        if d > 0:
            time.sleep(d)
        sub = now()
        fut = gw.invoke_decode_async(name, prompts[r.index], max_new=r.max_new,
                                     label=f"{label}:{r.index}")
        out.append(Served(r, due, sub, fut))
    return out


def _settle(served: List[Served], gw, deadline: float, label: str = "chipbench") -> None:
    from repro.core.metrics import now
    for s in served:
        try:
            s.tokens = np.asarray(s.future.result(max(deadline - now(), 0.001)))
        except Exception as e:  # noqa: BLE001 - a request that fails is counted
            s.error = repr(e)
            continue
        tls = gw.recorder.timelines(f"{label}:{s.req.index}")
        s.timeline = tls[0] if tls else None


def _read_trace(run: Run, tracer) -> None:
    from chipbench import trace_reduce
    a, b = tracer.span
    tr = trace_reduce.load(trace_reduce.find_xplane(WORK_DIR / "trace"))
    run.trace = trace_reduce.summarize(tr, window_s=b - a)
    run.trace_counters = tracer.counters
    run.row_lengths = row_lengths(run.lead_in + run.window,
                                  run.cell.config["function"]["prompt_len"], a, b)


def _check(run: Run, prompts, cfg: Dict, seed: int, control: bool) -> Dict:
    """The numbers compared, each with its limit, and ``correct``.

    With ``control`` the int8 reference's picks take the served tokens'
    place in the comparison (the served tokens' own gap is kept beside it as
    ``program_logit_gap``), so a sound control comes out not correct."""
    from chipbench.correct import sample, served_gap
    ok = [s for s in run.window if s.error is None]
    failed = len(run.window) - len(ok)
    for s in [s for s in run.window if s.error is not None][:3]:
        print(f"chipbench: request {s.req.index} failed: {s.error}", flush=True)
    checks = {"failed_requests": {"value": failed, "limit": 0}}
    readings = {}
    if ok:
        idx = sample([s.tokens for s in ok], seed)
        pick = [ok[i] for i in idx]
        fn = cfg["function"]
        readings = served_gap(cfg, weight_seed(seed),
                              [prompts[s.req.index][0] for s in pick],
                              [s.tokens for s in pick],
                              fn["prompt_len"] + fn["decode_steps"], control=control)
        gap = readings["control_logit_gap"] if control else readings["logit_gap"]
        checks["logit_gap"] = {"value": gap, "limit": cfg["logit_gap_limit"]}
        print(f"chipbench: compared {sum(len(s.tokens) for s in pick)} served tokens "
              f"of {len(pick)} requests", flush=True)
    correct = (bool(run.window) and failed == 0 and "logit_gap" in checks
               and checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"])
    return {"correct": bool(correct), "checks": checks,
            "program_logit_gap": readings.get("logit_gap") if control else None}


# -------------------------------------------------------------- knee sweep

def past_knee(row: Dict, lowest: Dict) -> bool:
    """Whether a swept rate is past the knee: a request due in its window was
    never served, or its TTFT p95 is over ``KNEE_TTFT_FACTOR`` times the
    lowest rate's."""
    return bool(row["unfinished"]) or \
        row["ttft_p95_ms"] > KNEE_TTFT_FACTOR * lowest["ttft_p95_ms"]


def _sweep(gw, spec, decoder, cell: Cell, seed: int, seconds: float,
           rates: List[float], now) -> Dict:
    """Offer each rate of a Poisson mix in turn, lowest first, on the resident
    tier, and read its tails and throughput. The knee is the highest rate
    whose TTFT p95 stays within ``KNEE_TTFT_FACTOR`` times the lowest rate's,
    with every request due in the window served: the sweep stops after the
    first rate past it."""
    tr = dict(cell.traffic)
    fn = cell.config["function"]
    rows, knee = [], None
    for rate in sorted(rates):
        label = f"sweep{rate:g}"
        tr["arrivals"] = {"kind": "poisson", "rate_rps": rate}
        sched = traffic_mod.schedule(tr, seed, seconds)
        prompts = {r.index: traffic_mod.prompt(seed, r.index, fn["prompt_len"],
                                               cell.config["vocab_size"]) for r in sched}
        gw.invoke_decode_async(spec.name, prompts[sched[0].index], max_new=min(
            KEEPALIVE_MAX_NEW, fn["decode_steps"]))
        t0 = now() + float(tr.get("lead_in_s", 0.0)) + 0.05
        sampler = _Sampler(None, decoder, t0, t0 + seconds, now)
        sampler.start()
        served = _drive(gw, spec.name, sched, prompts, t0, now, label)
        sampler.join()
        _settle(served, gw, t0 + seconds + LATE_WAIT_S, label)
        win = [s for s in served if s.req.due_s >= 0]
        ok = [s for s in win if s.timeline is not None]
        ttft = np.array([s.timeline.t_ttfr - s.due for s in ok]) if ok else np.array([np.inf])
        wait = np.array([s.timeline.t_dispatch - s.due if s.timeline is not None else np.inf
                         for s in win])
        done_in = [s for s in served if s.timeline is not None
                   and t0 <= s.timeline.t_done <= t0 + seconds]
        q = max(len(wait) // 5, 1)
        rows.append({
            "rate_rps": rate, "due": len(win), "unfinished": len(win) - len(ok),
            "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
            "ttft_p95_ms": float(np.percentile(ttft, 95) * 1e3),
            "wait_first_fifth_ms": float(np.median(wait[:q]) * 1e3),
            "wait_last_fifth_ms": float(np.median(wait[-q:]) * 1e3),
            "tokens_per_s": sum(len(s.tokens) for s in done_in) / seconds,
        })
        c0, c1 = sampler.counters["start"], sampler.counters["end"]
        rows[-1]["steps_per_s_in_window"] = (c1["steps"] - c0["steps"]) / seconds
        rows[-1]["occupancy"] = (c1["step_rows"] - c0["step_rows"]) / max(
            (c1["steps"] - c0["steps"]) * decoder.slots, 1)
        print(f"sweep: {json.dumps(rows[-1])}", flush=True)
        decoder.drain(600)
        if past_knee(rows[-1], rows[0]):
            break
        knee = rate
    return {"sweep": rows, "knee_rps": knee}
