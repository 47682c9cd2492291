"""One run of one cell: set-up, warm-up, the measured window, the
correctness check and the metrics.

Set-up draws the weights on the device from ``--seed`` in one jitted call,
runs ``ServingModel.prepare`` (backend pin, int8 decode images) and builds
the engine with the cell's page pool: ``(slots + 1) * nb + prefix_pages + 1``
pages of ``chunk`` tokens, where ``nb`` is one lane's pages and the prefix
store holds ``prefix_lanes`` lanes. Warm-up compiles every program and eager
op shape the window can use and nothing else: one small serve through the
cell's path, the admission-chunk programs at every quantum the engine can
choose (``chunk`` times 1..slots) and the page merge at every prompt length
the mix sends. A fresh engine and pool then serve the window's requests
through ``Engine.serve``.

The window opens when the backlog's first ``slots`` requests hold lanes
(their first tokens, admitted before it). It closes at the first token
stamped ``--seconds`` after it opened: that ``on_token`` callback cancels
every request, and cancellation lands at the next step boundary. Every
token is stamped on the host clock in ``on_token``, which fires once the
token is on the host, together with the engine's step count.

After the window, peak device memory is read, the program's state is freed,
and the plain reference judges a seeded sample of the finished requests
(``reference.py``): the same comparison, with the same limits, decides
``correct``. With ``--trace 1`` a profiler trace of a few seconds inside the
window yields the per-layer metrics, each read by its own file under
``metrics/``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import counts, reference, steps, workload, xplane

TRACE_DELAY_S = 1.0     # the trace opens this long after the window
TRACE_SECONDS = 3.0     # and closes this long after that (or at its end)
TRACE_DIR = workload.ROOT / ".chipbench" / "trace"
METRICS_DIR = workload.CHIP_DIR / "metrics"


class HarnessError(RuntimeError):
    """The run could not be measured as the cell asks."""


# ---------------------------------------------------------------- compiles


class CompileClock:
    """Counts JAX backend compiles."""

    def __init__(self) -> None:
        self.count = 0

    def __call__(self, event: str, _duration_secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


_CLOCK: CompileClock | None = None


def compile_clock() -> CompileClock:
    """The process's one compile listener (registered on first use)."""
    global _CLOCK
    if _CLOCK is None:
        import jax

        _CLOCK = CompileClock()
        jax.monitoring.register_event_duration_secs_listener(_CLOCK)
    return _CLOCK


def percentile(values, p: float):
    """Nearest-rank percentile: the smallest element with at least ``p``%
    of the sample at or below it."""
    xs = sorted(values)
    if not xs:
        return None
    k = max(0, -(-int(p) * len(xs) // 100) - 1)
    return xs[min(k, len(xs) - 1)]


# ------------------------------------------------------------------ window


class Window:
    """Host-clock stamps of every token, and the window's open and close."""

    def __init__(self, eng, n_requests: int, slots: int, seconds: float,
                 trace_dir: Path | None):
        self.eng = eng
        self.n = n_requests
        self.slots = slots
        self.seconds = seconds
        self.stamps: list[list[tuple[float, int]]] = [[] for _ in range(n_requests)]
        self.started = 0
        self.t0 = self.t_end = None
        self.count0 = self.count_end = None
        self.compiles0 = self.compiles_end = None
        self.trace_dir = trace_dir
        self.trace_span = None
        self.trace_t0 = None
        self.trace_counts: tuple[int, int] | None = None

    def open(self, t: float) -> None:
        self.t0 = t
        self.count0 = len(self.eng.events)
        self.compiles0 = compile_clock().count

    def callback(self, i: int):
        def on_token(_tok: int) -> None:
            t = time.perf_counter()
            k = len(self.eng.events)
            own = self.stamps[i]
            own.append((t, k))
            if len(own) == 1:
                self.started += 1
                if self.t0 is None and self.started == self.slots:
                    self.open(t)
            if self.t0 is None or self.t_end is not None:
                return
            if self.trace_dir is not None:
                self._trace_tick(t, k)
            if t >= self.t0 + self.seconds:
                self.close(t, k)
        return on_token

    def _trace_tick(self, t: float, k: int) -> None:
        import jax

        if self.trace_t0 is None and t >= self.t0 + TRACE_DELAY_S:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # keep the host loop's own pace
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
            self.trace_span = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            self.trace_span.__enter__()
            self.trace_t0 = t
            self.trace_counts = (k, k)
        elif (self.trace_span is not None
              and t >= self.trace_t0 + TRACE_SECONDS):
            self._stop_trace(k)

    def _stop_trace(self, k: int) -> None:
        import jax

        self.trace_span.__exit__(None, None, None)
        self.trace_span = None
        self.trace_counts = (self.trace_counts[0], k)
        jax.profiler.stop_trace()

    def close(self, t: float, k: int) -> None:
        self.t_end = t
        self.count_end = k
        self.compiles_end = compile_clock().count
        if self.trace_span is not None:
            self._stop_trace(k)
        for j in range(self.n):
            self.eng.cancel(j)

    # -------------------------------------------------------------- metrics

    def _inside(self, t: float) -> bool:
        return self.t0 < t <= self.t_end

    def tokens_per_s(self) -> float:
        n = sum(1 for own in self.stamps for t, _ in own if self._inside(t))
        return n / (self.t_end - self.t0)

    def tpot_gaps(self) -> list[float]:
        return [b[0] - a[0] for own in self.stamps for a, b in zip(own, own[1:])
                if self._inside(a[0]) and self._inside(b[0])]

    def pace(self, slice_s: float = 5.0) -> dict:
        """How steadily the host loop ran: engine steps that emitted inside
        the window per slice of ``slice_s`` seconds, and the longest gap
        between the last tokens of two such steps."""
        ends: dict[int, float] = {}
        for own in self.stamps:
            for t, k in own:
                if self._inside(t):
                    ends[k] = max(t, ends.get(k, t))
        ts = sorted(ends.values())
        per = [0] * (int((self.t_end - self.t0) // slice_s) + 1)
        for t in ts:
            per[int((t - self.t0) // slice_s)] += 1
        gap = max((b - a for a, b in zip(ts, ts[1:])), default=0.0)
        return {"steps_per_slice": per, "slice_s": slice_s,
                "longest_step_gap_ms": 1e3 * gap}


# ------------------------------------------------------------------ set-up


@dataclass
class Setup:
    cfg: object
    sm: object
    mode: object
    slots: int
    max_len: int
    chunk: int
    prefix_pages: int


def model_config(config: dict, backend: str):
    from repro.configs import get_config

    serving = config["serving"]
    return get_config(config["registered"]).replace(
        **workload.model_overrides(config),
        quantized_decode=bool(serving["quantized_decode"]),
        attn_backend=backend)


def prepare(cell: workload.Cell, seed: int, backend: str) -> Setup:
    """Weights drawn on the device from the seed, then the serving
    artifact."""
    import jax
    import jax.numpy as jnp

    from repro.core.pim_modes import Mode
    from repro.models import model as M
    from repro.serve.serving_model import ServingModel

    cfg = model_config(cell.config, backend)
    tr = cell.traffic
    key = jnp.asarray(workload.seed_words(seed))
    params = jax.jit(M.init_params, static_argnums=1)(key, cfg)
    sm = ServingModel.prepare(cfg, params, max_len=tr["max_len"],
                              slots=tr["slots"])
    jax.block_until_ready((sm.params, sm.decode_params))
    if sm.backend != backend:
        raise HarnessError(f"prepare pinned {sm.backend!r}, asked {backend!r}")
    chunk = int(cell.config["serving"]["chunk"])
    nb = -(-tr["max_len"] // chunk)
    return Setup(cfg=sm.cfg, sm=sm, mode=Mode(tr["mode"]), slots=tr["slots"],
                 max_len=tr["max_len"], chunk=chunk,
                 prefix_pages=int(cell.config["serving"]["prefix_lanes"]) * nb)


def engine(s: Setup):
    from repro.serve.engine import Engine

    pool = s.sm.cache_pool(slots=s.slots, prefix_cache=True,
                           block_size=s.chunk, prefix_pages=s.prefix_pages)
    return Engine(s.cfg, s.sm.params, max_len=s.max_len, slots=s.slots,
                  mode=s.mode, chunk=s.chunk, serving=s.sm, pool=pool)


def _greedy(prompt, max_new: int):
    from repro.serve.api import GenerationRequest

    return GenerationRequest(prompt=[int(t) for t in prompt],
                             max_new_tokens=max_new)


def _distinct(i: int, n: int, vocab: int) -> list[int]:
    """A prompt whose first page no other warm-up prompt shares, so the
    prefix store never diverts one from the path it is meant to warm."""
    return [1 + (i + t) % (vocab - 1) for t in range(n)]


def warm(s: Setup, traffic: dict) -> None:
    """Compile every shape the window can reach, on a throwaway engine."""
    import jax
    import jax.numpy as jnp

    from repro.core import interleave
    from repro.core.pim_modes import Mode
    from repro.serve import sampling

    eng = engine(s)
    c = s.chunk
    # the backlog path: a batch admission, decode steps, one streamed prompt
    eng.serve([_greedy(_distinct(i, c, s.cfg.vocab_size), 4)
               for i in range(s.slots)]
              + [_greedy(_distinct(s.slots, 2 * c, s.cfg.vocab_size), 2)])
    pool = eng.pool
    dparams = s.sm.decode_params
    feed = jnp.zeros((s.slots, 1), jnp.int32)
    for m in range(1, s.slots + 1):
        # the admission quantum grows with free lanes: chunk x 1..slots
        q = c * m
        staging, _ = pool.stage_admission([0] * (q + 1))
        staging = pool.staging_step_prep(staging, q)
        toks = jnp.zeros((1, q), jnp.int32)
        views = pool.views(span=1)
        outs = []
        if s.mode is Mode.LBIM:
            outs.append(interleave.fused_step(dparams, views, feed, staging,
                                              toks, s.cfg)[2])
        outs.append(interleave.prefill_chunk_step(dparams, staging, toks,
                                                  s.cfg)[0])
        for pre in outs:
            last = pre[:, -1:, :]
            np.asarray(last)
            sampling.greedy(last)
        pool.release_staging()
    for n in workload.prompt_lengths(traffic, c):
        # the merge of a drained admission stream copies its fresh pages
        staging, _ = pool.stage_admission([0] * (n + 1))
        staging = pool.staging_step_prep(staging, n)
        si = pool.alloc(_greedy(range(n), 1), 0)
        pool.insert(si, staging)
        pool.retire(si)
    jax.block_until_ready(pool.views()["k_pages"])


# --------------------------------------------------------------------- run


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    used = devs[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def sample_finished(results, reqs, k: int, seed: int, slots: int) -> list[int]:
    """Up to ``k`` finished requests: the backlog's first ``slots`` (admitted
    together, one to each lane, so every lane is compared), the one that
    served the most tokens, and others drawn from the seed."""
    from repro.serve.api import RequestState

    if k < slots + 1:
        raise HarnessError(f"check_requests {k} must cover the {slots} lanes "
                           "and the longest request")
    done = [i for i, r in enumerate(results) if r.state is RequestState.FINISHED]
    if not done:
        return []
    longest = max(done, key=lambda i: (len(results[i].tokens), len(reqs[i].prompt)))
    pick = [i for i in done if i < slots]
    if longest not in pick:
        pick.append(longest)
    rest = [i for i in done if i not in pick]
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(3)[2])
    pick += [rest[i] for i in rng.permutation(len(rest))[:max(0, k - len(pick))]]
    return pick


def health_checks(eng, backend: str) -> dict:
    h = eng.health()
    faults = sum(v["fallbacks"] + v["nan_trips"] + v["kernel_faults"]
                 for v in h["ladder"].values())
    off = sum(1 for v in h["ladder"].values() if v["backend"] != backend)
    return {"ladder_faults": (faults, 0), "ops_off_backend": (off, 0),
            "degraded_or_failed_steps": (int(h["degraded"])
                                         + h["counters"]["failures"], 0),
            "pool_invariant_violations": (len(eng.pool.check_invariants()), 0)}


def judge(checks: dict) -> bool:
    """``correct``: every compared number finite and within its limit."""
    return all(np.isfinite(v) and v <= lim for v, lim in checks.values())


def load_metric(name: str):
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    if spec is None or not path.exists():
        raise HarnessError(f"no reader {path} for per-layer metric {name!r}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(cell: workload.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, backend: str = "pallas",
        with_control: bool = False) -> dict:
    """One measured run; returns the result line's object. ``with_control``
    also puts each control (``reference.CONTROLS``) in the program's place
    on the same sample and judges it by the same checks and limits, under
    ``controls`` (``control.py``; the benchmark's own runs never do)."""
    import jax

    from repro.serve.api import RequestState
    from repro.serve.api import TERMINAL_STATES

    compile_clock()   # listening before anything compiles
    s = prepare(cell, seed, backend)
    warm(s, cell.traffic)
    gc.collect()   # the warm-up pool goes before the window's is made
    tr = cell.traffic
    reqs_spec = workload.generate(tr, seed, seconds, s.cfg.vocab_size, s.chunk)
    eng = engine(s)
    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        trace_dir = TRACE_DIR
    win = Window(eng, len(reqs_spec), s.slots, seconds, trace_dir=trace_dir)
    reqs = []
    for i, r in enumerate(reqs_spec):
        q = _greedy(r.prompt, r.max_new)
        q.on_token = win.callback(i)
        reqs.append(q)
    results = eng.serve(reqs)
    if win.t0 is None or win.t_end is None:
        raise HarnessError(
            "the requests ran out before the window closed: raise the mix's "
            "requests_per_s_ceiling")
    device = device_info(cell.chips)

    # ---- what the window measured
    admit = [r.admit_step for r in results]
    e2e = {
        "setup_s": win.t0 - t_start,
        "tokens_per_s": win.tokens_per_s(),
        "tpot_p95_ms": 1e3 * percentile(win.tpot_gaps(), 95),
        "peak_hbm_gb": device["memory_peak_bytes"] / 1e9,
    }
    window_events = eng.events[win.count0:win.count_end]
    checks = health_checks(eng, backend)
    states = [r.state for r in results]
    checks["requests_not_terminal"] = (
        sum(1 for st in states if st not in TERMINAL_STATES), 0)
    failed = sum(1 for st in states
                 if st in (RequestState.FAILED, RequestState.TIMED_OUT))
    attempted = sum(1 for st in states if st is not RequestState.CANCELLED)
    checks["failed_requests"] = (failed, 0)
    stamps = [[(k, j) for j, (_, k) in enumerate(own)] for own in win.stamps]
    plens = [len(q.prompt) for q in reqs]
    reused = [r.reused_prefix_tokens for r in results]
    all_steps = steps.rebuild(eng.events, stamps, admit, plens, reused)
    pick = sample_finished(results, reqs, int(tr["check_requests"]), seed,
                           s.slots)
    seqs = [(reqs[i].prompt, results[i].tokens) for i in pick]
    n_events = len(eng.events)
    compiles = win.compiles_end - win.compiles0
    finished = sum(1 for st in states if st is RequestState.FINISHED)

    # ---- free the program's state, then the reference
    win.eng = None
    del eng, s, reqs, results
    gc.collect()
    dims = reference.Dims.from_config(cell.config)
    limit = float(cell.limits["max_logit_gap"]["limit"])
    controls = {}
    if seqs:
        batch = reference.make_batch(
            seqs, int(tr["check_requests"]), tr["max_len"],
            int(tr["check_requests"]) * int(tr["output_tokens"]["hi"]))
        key = jax.numpy.asarray(workload.seed_words(seed))
        gap = reference.served_gap(key, dims, batch)
        served = int(batch.valid.sum())
        if with_control:
            for name, g in reference.control_gaps(key, dims, batch).items():
                c = dict(checks, max_logit_gap=(g, limit))
                controls[name] = {"correct": judge(c), "max_logit_gap": g}
    else:
        gap, served = float("inf"), 0
    checks["max_logit_gap"] = (gap, limit)
    correct = judge(checks)

    out = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    if not trace:
        missing = [m["name"] for m in cell.end_to_end if e2e.get(m["name"]) is None]
        if missing:
            raise HarnessError(f"the window gave no sample for {missing}")
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                          for m in cell.end_to_end}
    else:
        summary = xplane.summarize(xplane.load(str(trace_dir)))
        k0, k1 = win.trace_counts
        ctx = SimpleNamespace(
            summary=summary, steps=all_steps[k0:k1], dims=dims,
            peaks=counts.peaks(device["kind"]), slots=tr["slots"],
            window_events=window_events, window_compiles=compiles)
        metrics = {}
        for m in cell.per_layer:
            v = load_metric(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": xplane.top_ops(summary),
                            "idle_gaps": [[n, v] for n, v in summary.gaps]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    out["device"] = device
    out["window"] = {"steps": win.count_end - win.count0, "events": n_events,
                     "finished": finished, "compared_tokens": served,
                     "compiles": compiles, **win.pace()}
    if with_control:
        out["controls"] = controls
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out


def emit(result: dict) -> None:
    """Checks as the last lines of stderr, the result as the last line of
    stdout."""
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
