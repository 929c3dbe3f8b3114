"""One run of one cell: set-up, the measured window, the checks, the
metrics and the result line.

Everything that belongs to a configuration, a traffic mix or a metric is
a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — the deployment: schema, scale, backend and,
  for an LM backend, the model's sizes, its family module (``"family"``,
  a path from the checkout's root; ``reference/lm.py`` says what such a
  module gives) and the logit-gap limit;
* ``traffic/<traffic>.json`` — the templates of a cycle, the cache scope
  (``per_query`` or ``shared``) and the warm-up cycles;
* ``queries/<schema>.json`` — the templates, as data;
* ``metrics/<metric>.py`` — a reader ``read(run) -> float | None``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Optional

import jax
import numpy as np

from . import data as datagen
from . import systems, window
from .clock import CompileClock, process_start_epoch
from .peaks import peaks_for
from .plans import build_plan, load_templates
from .reference import lm
from .reference.relational import Relational, mismatch
from .tracing import Spans, read_trace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
WINDOW_SPAN = "bench.window"
# requests of the window the LM reference re-runs, the longest included
LM_SAMPLE = 768
# prompts of each predicate the verdict head is chosen from
HEAD_SAMPLE = 64
_PLACEHOLDER = re.compile(r"\{([A-Za-z_]\w*)\.([A-Za-z_]\w*)\}")


@dataclass
class Run:
    """What a metric reader gets: the window's records and clocks, the
    spans, the trace summary (traced runs only) and the sizes."""

    records: list
    t0: float
    t1: float
    setup_s: float
    peak_bytes: int
    spans: Spans
    peaks: dict
    templates: dict
    table_rows: dict
    trace: object = None
    serving: Optional[dict] = None
    served: Optional[list] = None
    model: Optional[dict] = None
    family: Optional[ModuleType] = None

    @property
    def window_s(self) -> float:
        """Seconds of the measured window."""
        return self.t1 - self.t0

    @property
    def n(self) -> int:
        """Queries completed in the window."""
        return len(self.records)


def configure_cache() -> str:
    """Keep JAX's persistent compilation cache at the fixed path
    ``<checkout>/.jax_cache``, unless the environment names one, and keep
    every compile, eager ones included. Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_json(path: Path) -> dict:
    """Parse one JSON file."""
    return json.loads(Path(path).read_text())


def cell(bench: dict, name: str) -> tuple[dict, dict]:
    """The workload ``name`` and its configuration entry."""
    for w in bench["workloads"]:
        if w["name"] == name:
            for c in bench["configs"]:
                if c["name"] == w["config"]:
                    return w, c
            raise KeyError(f"no configuration {w['config']!r}")
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, name: str, trace: bool) -> list[dict]:
    """The metrics a run of workload ``name`` reports."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def _load(path: Path, name: str) -> ModuleType:
    """The Python file at ``path``, executed as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(metric: dict, run: Run):
    """Value of ``metric`` from its reader, or None if it found nothing."""
    return _load(HERE / "metrics" / f"{metric['name']}.py",
                 "perfbench_metric_" + metric["name"].replace(".", "_")
                 ).read(run)


def load_family(config: dict, root: Path = ROOT) -> ModuleType:
    """The model family module an LM configuration names under
    ``"family"``, a path from the checkout's ``root``."""
    path = config.get("family")
    if not path:
        raise ValueError(
            f"configuration {config.get('name')!r} has an LM backend and no "
            f"\"family\" key: name its model family module, a path from the "
            f"checkout's root such as \"perfbench/reference/dense.py\"")
    return _load(root / path, "perfbench_family_" + re.sub(r"\W", "_", path))


def predicates(templates: dict) -> set[str]:
    """Names of the semantic predicates the templates use."""
    names = set()

    def walk(steps):
        for kind, *args in steps[1:]:
            if kind == "sem_filter":
                names.add(args[0])
            elif kind in ("join", "cross", "sem_join"):
                walk(args[0])
                if kind == "sem_join":
                    names.add(args[1])
    for t in templates.values():
        walk(t["plan"])
    return names


def longest_prompt_tokens(data, templates: dict) -> int:
    """Tokens (BOS, one per word, SEP) of the longest prompt any of the
    templates' semantic predicates can render over the data."""
    best = 0
    for name in predicates(templates):
        phi = data.prompts[name]
        n = len(phi.split())
        for t, c in _PLACEHOLDER.findall(phi):
            n += max(len(str(v).split())
                     for v in data.tables[t][c].tolist()) - 1
        best = max(best, n + 2)
    return best


def sample_prompts(data, templates: dict, n: int) -> list[list[str]]:
    """Per semantic predicate of the templates (in name order), its
    prompt rendered over the first ``n`` rows of its table."""
    groups = []
    for name in sorted(predicates(templates)):
        phi = data.prompts[name]
        (t, c), *_ = _PLACEHOLDER.findall(phi)
        groups.append([_PLACEHOLDER.sub(lambda _, v=v: str(v), phi)
                       for v in data.tables[t][c][:n].tolist()])
    return groups


def served_verdicts(served: list) -> dict:
    """prompt -> whether the LM answered YES: its first served token."""
    return {p: bool(toks) and toks[0] == lm.YES for p, toks in served}


def _warm_serving(system, data, templates) -> None:
    """Admit one batch of every power-of-two width the scheduler uses, so
    no prefill-insert shape first compiles inside the window."""
    eng = system.engine
    if eng is None:
        return
    prompts = [p for g in sample_prompts(data, templates, eng.batch_size)
               for p in g]
    width = eng.batch_size
    while width >= 1:
        eng.answer(prompts[:width])
        width //= 2


def _sample_served(served: list, seed: int, k: int) -> list:
    """``k`` distinct served (prompt, tokens) drawn from ``seed``, the
    longest first: the first ``j`` of a larger draw are the draw of
    ``j``."""
    served = list(dict.fromkeys((p, tuple(t)) for p, t in served))
    rng = np.random.default_rng((int(seed) % 2**64, 0x1A6))
    longest = max(range(len(served)), key=lambda i: len(served[i][0].split()),
                  default=0)
    order = [i for i in rng.permutation(len(served)) if i != longest]
    return [(p, list(t)) for p, t in
            (served[i] for i in ([longest] + order)[:k])] if served else []


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, require_tpu: bool = True,
             bench: Optional[dict] = None, configs: Optional[dict] = None,
             traffics: Optional[dict] = None,
             start_epoch: Optional[float] = None,
             control: tuple = (), lm_sample: int = LM_SAMPLE) -> dict:
    """Run one cell and return its result line (a dict). ``bench`` and
    ``configs`` (name -> config dict) replace the files, for rehearsals
    at small sizes, and ``traffics`` (name -> traffic dict) the traffic
    files. ``control`` (precisions, e.g. ``("int8",)``) puts the
    reference computed in the first of them in the served model's place
    for the logit check, as ``control.py`` does; the program's own
    reading is kept beside it as ``lm_logit_gap_mean_served`` and every
    precision's as ``lm_logit_gap_mean_<precision>``. ``lm_sample`` is the
    number of served requests the logit check re-runs."""
    start_epoch = start_epoch or process_start_epoch()
    bench = bench or load_json(root / "BENCHMARK.json")
    work, centry = cell(bench, workload)
    config = (configs or {}).get(centry["name"]) or load_json(
        root / centry["file"])
    traffic = (traffics or {}).get(work["traffic"]) or load_json(
        HERE / "traffic" / f"{work['traffic']}.json")
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < work["chips"]):
        raise SystemExit(f"perfbench: {workload} needs {work['chips']} TPU "
                         f"chip(s); JAX has {len(devs)} {devs[0].platform} "
                         f"device(s)")
    dev = devs[0]
    clock = CompileClock()
    spans = Spans()
    log = _Log()

    # the data are drawn once from the configuration's data seed; the run's
    # seed shuffles every table's rows, the cycles and the checked sample,
    # and draws the weights
    data = datagen.generate(config["schema"], config["data_seed"],
                            config["scale"]).permuted(seed)
    all_templates = load_templates(config["schema"])
    templates = {n: all_templates[n] for n in traffic["templates"]}
    longest = longest_prompt_tokens(data, templates)
    family = load_family(config, root) if config["backend"] == "lm" else None
    system = systems.build(config, data, seed, spans, family=family,
                           longest_prompt=longest,
                           groups=sample_prompts(data, templates,
                                                 HEAD_SAMPLE))
    ref = Relational(data, importlib.import_module(
        f"{datagen.__name__}.{config['schema']}").LATENT)
    per_query = traffic["scope"] == "per_query"

    def run_query(name: str) -> dict:
        tpl = templates[name]
        n0 = len(system.engine.served) if system.engine else 0
        with spans.span("bench.query"):
            t0 = time.perf_counter()
            if per_query:
                system.front.reset_scope()
            with spans.span("bench.plan"):
                plan = system_optimize(build_plan(tpl, data.prompts),
                                       system.catalog)
            with spans.span("bench.execute"):
                table, stats = system.front.execute(plan)
                jax.block_until_ready(table.columns)
            t1 = time.perf_counter()
        return {"template": name, "t0": t0, "t1": t1, "latency_s": t1 - t0,
                "stats": stats, "table": table, "served": (n0, len(
                    system.engine.served) if system.engine else 0)}

    # ------------------------------------------------------------ set-up
    warm_orders = window.cycle_orders(traffic["templates"], seed + 1)
    for _ in range(traffic["warmup_cycles"]):
        for name in next(warm_orders):
            run_query(name)
    _warm_serving(system, data, templates)
    log.line(f"[setup] {workload} seed={seed} device={dev.device_kind} "
             f"count={len(devs)} longest_prompt={longest} "
             + " ".join(f"{k}={v}" for k, v in clock.snapshot().items()))

    # ------------------------------------------------------------ window
    eng = system.engine
    s0 = _serving_counts(eng)
    n_served0 = len(eng.served) if eng is not None else 0
    c0 = clock.snapshot()
    logdir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=opts)
    # what set-up made is kept out of the collector's scans, so that a
    # collection in the window walks only what the window made
    gc.collect()
    gc.freeze()
    pauses = _GcPauses()
    gc.callbacks.append(pauses)
    setup_s = time.time() - start_epoch
    try:
        with spans.span(WINDOW_SPAN):
            records, t0, t1 = window.run_window(
                window.cycle_orders(traffic["templates"], seed), run_query,
                seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
        gc.callbacks.remove(pauses)
        gc.unfreeze()
    c1 = clock.snapshot()
    in_window = {k: c1[k] - c0[k] for k in c1}
    log.line(f"[window] queries={len(records)} seconds={t1 - t0:.4f} "
             + " ".join(f"{k}_in_window={v}" for k, v in in_window.items())
             + f" gc_oldest={pauses.oldest} gc_s={pauses.seconds:.4f} "
             + " ".join(_latencies(records)))
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:work["chips"]])
    serving = None
    served = None
    if eng is not None:
        s1 = _serving_counts(eng)
        serving = {k: s1[k] - s0[k] for k in s1}
        served = eng.served[n_served0:]

    summary = None
    if trace:
        try:
            summary = read_trace(logdir, WINDOW_SPAN)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    # ------------------------------------------------------------ checks
    failed, why, answers = 0, {}, {}
    for rec in records:
        tpl = templates[rec["template"]]
        rows = system.db.materialize(rec.pop("table"), tpl["out"])
        if eng is None:
            ans = answers.get(rec["template"])
            if ans is None:
                ans = answers[rec["template"]] = ref.answer(tpl)
        else:
            # the verdicts of the prompts served while the query ran
            ans = ref.answer(tpl, verdicts=served_verdicts(
                eng.served[slice(*rec["served"])]))
        bad = mismatch(rows, tpl["out"], ans)
        if bad:
            failed += 1
            why.setdefault(rec["template"], bad)
    for name, bad in why.items():
        log.line(f"[check] {name}: {bad}")
    if eng is not None:
        log.line("[check] verdicts " + " ".join(_verdict_shares(
            records, eng.served)))
    checks = {"queries_wrong": {"value": failed, "limit": 0}}

    run = Run(records=records, t0=t0, t1=t1, setup_s=setup_s,
              peak_bytes=peak, spans=spans, peaks=peaks_for(dev.device_kind)
              if dev.platform == "tpu" else {}, templates=templates,
              table_rows={t: data.num_rows(t) for t in data.tables},
              trace=summary,
              serving=serving, served=served, model=system.model,
              family=family)
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        v = read_metric(m, run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    if eng is not None:
        model, head = system.model, system.head
        sample = _sample_served(served, seed, lm_sample)
        del system, eng, run
        gc.collect()
        weights = lm.with_head(family.init_weights(model, seed), head)
        gaps = lm.served_gaps(family, model, weights, sample,
                              length=longest + 1)
        log.line(f"[check] lm tokens={len(gaps)} requests={len(sample)} "
                 f"served_below_best={int((gaps > 0).sum())} served_gap_max"
                 f"{_by_sample(gaps, sample, np.max)} served_gap_mean"
                 f"{_by_sample(gaps, sample, np.mean)}")
        limit = config["lm_logit_gap_mean_limit"]
        if control:
            checks["lm_logit_gap_mean_served"] = {
                "value": float(gaps.mean()), "limit": limit}
            for mode in control[::-1]:
                gaps = lm.served_gaps(family, model, weights, sample,
                                      quant=mode, length=longest + 1)
                log.line(f"[check] lm {mode} below_best="
                         f"{int((gaps > 0).sum())} gap_max"
                         f"{_by_sample(gaps, sample, np.max)} gap_mean"
                         f"{_by_sample(gaps, sample, np.mean)}")
                checks[f"lm_logit_gap_mean_{mode}"] = {
                    "value": float(gaps.mean()), "limit": limit}
        del weights
        checks["lm_logit_gap_mean"] = {"value": float(gaps.mean()),
                                       "limit": limit}

    correct = failed == 0 and all(
        c["value"] <= c["limit"] for n, c in checks.items()
        if n == "queries_wrong" or n == "lm_logit_gap_mean")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": work["chips"], "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    for name, c in checks.items():
        log.line(f"[check] {name} {c['value']} limit {c['limit']}")
    return result


def system_optimize(plan, catalog):
    """The PLOP planner's cost-based placement (the program's entry)."""
    from repro.core import optimize
    return optimize(plan, catalog, strategy="cost").plan


def _by_sample(gaps: np.ndarray, sample: list, stat) -> str:
    """``stat`` (the widest or the mean) of the gaps over the first 192,
    384, ... requests of the sample, as ``@<requests>=<gap>`` words."""
    ends = np.cumsum([len(t) for _, t in sample])
    out, k = [], 192
    while True:
        k = min(k, len(sample))
        out.append(f"@{k}={float(stat(gaps[:ends[k - 1]])):.6g}")
        if k == len(sample):
            return " ".join(out)
        k *= 2


def _verdict_shares(records: list, served: list) -> list[str]:
    """Per template, the share of its served prompts answered YES, and of
    all, the share answered YES or NO at the first token."""
    yes, n, first = {}, {}, 0
    for rec in records:
        toks = [t for _, t in served[slice(*rec["served"])]]
        name = rec["template"]
        yes[name] = yes.get(name, 0) + sum(t[:1] == [lm.YES] for t in toks)
        n[name] = n.get(name, 0) + len(toks)
        first += sum(t[:1] in ([lm.YES], [lm.NO]) for t in toks)
    out = [f"yes_share.{k}={yes[k] / n[k]:.4f}" for k in sorted(n) if n[k]]
    return out + [f"answered_at_once={first / max(1, sum(n.values())):.4f}"]


def _latencies(records: list) -> list[str]:
    """Per template, its queries' least, median and largest latency in
    seconds, as ``latency_s.<template>=<min>/<median>/<max>`` words."""
    by = {}
    for rec in records:
        by.setdefault(rec["template"], []).append(rec["latency_s"])
    return [f"latency_s.{k}=" + "/".join(
        f"{f(v):.4f}" for f in (min, np.median, max))
        for k, v in sorted(by.items())]


class _GcPauses:
    """A ``gc.callbacks`` entry: the collections of the oldest generation
    and the seconds that all collections took."""

    def __init__(self):
        self.oldest = 0
        self.seconds = 0.0
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
            return
        self.seconds += time.perf_counter() - self._t
        self.oldest += info["generation"] == 2


def _serving_counts(eng) -> dict:
    if eng is None:
        return {}
    s = eng.stats
    return {"prefill_tokens": s.prefill_tokens,
            "prefill_token_slots": s.prefill_token_slots}


class _Log:
    """Lines for standard error, flushed as they come."""

    def line(self, text: str) -> None:
        print(text, file=sys.stderr, flush=True)
