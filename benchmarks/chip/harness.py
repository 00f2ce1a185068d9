"""One run of one cell: the program's front door, ``repro.exp.run``, driven
from data files found by name, then checked against the plain reference.

The harness changes nothing in the program. It wraps two of its
functions for the length of a run:

* ``exp.build.build`` -- the model is built from the configuration the
  file states (its ``overrides`` set on the program's registered config),
  and its ``init``, which runs only in set-up, returns the weights the
  benchmark makes from the seed. The program's own token stream feeds
  every step;
* ``core.driver.run_loop`` -- the loop the program runs is cut into
  segments of the same call: the first step and the next two (the readings
  the reference is compared with are taken between them), the warm-up that
  meets every compiled program once, and then the measured window or the
  traced stretch. Segments continue the step count and the gossip round,
  so the program runs exactly the loop it would run in one call. The
  loop's data, step and record calls are timed on the host, to name the
  phase of a step that stalls.

A cell's files are found by name under a root (``benchmarks/chip/`` by
default): ``configs/<config>.json`` and its module ``configs/<config>.py``,
``traffic/<traffic>.json`` and ``limits/<workload>.json``. The module holds
the plain reference's ``loss(params, batch, cfg)`` and may state what the
harness needs to know of its model (see ``Cell``).

``run_cell`` returns the result object the command prints.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import counting  # noqa: E402
import inputs  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

# the steps a spec asks for: they size the realized schedule's horizon
# (a mobile schedule's period); the loop runs past them and the schedule
# wraps, as a restored run's does
SPEC_STEPS = 64
TRACE_SECONDS = 3.0


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_entry(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bench['workloads']]})")


@dataclasses.dataclass
class Cell:
    """One workload's files, found by name under ``root``.

    ``model`` is the configuration's module ``configs/<config>.py``. Its
    ``loss(params, batch, cfg)`` is the plain reference; besides, it may
    state:

    * ``WEIGHTS``: how its leaves are drawn, before the built-in rule
      (``inputs.WeightRule``);
    * ``forward_flops(cfg, tokens)``: forward matmul FLOPs of one sequence
      (``counting.step_flops``; the built-in count otherwise);
    * ``sizes(program_cfg)``: the file's size keys as a program config
      holds them, read at a preset other than ``full`` (CPU tests), where
      the program runs smaller than the file states.
    """

    name: str
    cfg: dict
    model: types.ModuleType
    traffic: dict
    root: str

    @property
    def limits(self) -> dict:
        return load_json(self.root, "limits", self.name + ".json")

    @property
    def rule(self):
        return inputs.WeightRule(getattr(self.model, "WEIGHTS", None),
                                 origin=self.model.__file__)

    def cfg_at(self, program_cfg, preset: str) -> dict:
        """The configuration as the run builds it: the file's, or at a
        smaller preset, with the sizes the program holds there."""
        if preset == "full" or not hasattr(self.model, "sizes"):
            return self.cfg
        return {**self.cfg, **self.model.sizes(program_cfg)}


def config_module(root: str, config: str) -> types.ModuleType:
    return load_module(os.path.join(root, "configs", config + ".py"),
                       "config_" + config.replace(".", "_").replace("-", "_"))


def find_cell(bench: dict, workload: str, root: str = HERE) -> Cell:
    entry = bench_entry(bench, workload)
    return Cell(name=workload,
                cfg=load_json(root, "configs", entry["config"] + ".json"),
                model=config_module(root, entry["config"]),
                traffic=load_json(root, "traffic", entry["traffic"] + ".json"),
                root=root)


def peaks_for(kind: str) -> dict:
    table = load_json(HERE, "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device_kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][kind]


class Compiles:
    """Counts JAX's backend compilations (each program compiled or loaded
    from the persistent cache, as ``chip_smoke.py`` counts them) and the
    cache's hits and misses, so the window can be shown to compile nothing
    and a second run to find every program in the cache. Class-level:
    JAX's listeners are registered once per process and cannot be
    removed."""

    count = hits = misses = 0
    _on = False

    @classmethod
    def watch(cls):
        if cls._on:
            return
        cls._on = True

        def on_duration(event, sec, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.count += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                cls.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                cls.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def leaf_norms(tree) -> np.ndarray:
    """(leaves, n) norms of every node's slice of every leaf of a
    node-stacked tree (the program's state)."""
    f = jax.jit(lambda t: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)).reshape(
            l.shape[0], -1), axis=1)) for l in jax.tree.leaves(t)]))
    return np.asarray(f(tree), np.float64)


def spread_norms(tree) -> np.ndarray:
    """(leaves, n) norms of every node's slice of every leaf, less the
    nodes' mean, of a node-stacked tree."""
    f = jax.jit(lambda t: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(l - jnp.mean(l, 0, keepdims=True)).reshape(
            l.shape[0], -1), axis=1)) for l in jax.tree.leaves(t)]))
    return np.asarray(f(tree), np.float64)


def change_norms(x, shapes, seed, rule) -> np.ndarray:
    """(leaves, n) norms of x - x0 per node: x node-stacked, x0 the seed's
    weights, made inside the same program so that no copy of them is kept
    beside the state."""
    f = jax.jit(lambda a, key: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(u.astype(jnp.float32) - v[None]).reshape(
            u.shape[0], -1), axis=1))
        for u, v in zip(jax.tree.leaves(a),
                        inputs.weight_leaves(shapes, key, rule))]))
    return np.asarray(f(x, inputs.weights_key(seed)), np.float64)


def make_spec(cfg: dict, traffic: dict, seed: int, preset: str):
    from repro import exp

    return exp.ExperimentSpec(
        model=exp.ModelRef(kind="arch", arch=cfg["arch"], preset=preset),
        data=exp.DataSpec(batch=traffic["batch"], seq=traffic["seq"],
                          active_vocab=0),
        algorithm=exp.AlgorithmSpec(name=traffic["algorithm"],
                                    gamma=traffic["gamma"], R=traffic["R"]),
        topology=exp.TopologySpec(**traffic["topology"]),
        channel=exp.ChannelSpec(**traffic.get("channel", {})),
        run=exp.RunSpec(steps=SPEC_STEPS, nodes=traffic["nodes"], seed=seed,
                        gossip_impl=traffic["gossip_impl"]))


def check_program_config(cfg: dict, program_cfg) -> None:
    """The program runs the configuration the file states, or no run."""
    bad = {k: (v, getattr(program_cfg, k)) for k, v in cfg["program"].items()
           if getattr(program_cfg, k) != v}
    if bad:
        raise ValueError(f"the program's {cfg['arch']} departs from "
                         f"{cfg['name']}.json: {bad}")


class Plan:
    """What one run does in the program's loop, and what it read there."""

    def __init__(self, *, seconds, trace, check_only, t0=None, spread=True):
        self.seconds, self.trace, self.check_only = seconds, trace, check_only
        self.spread = spread  # read the trackers' spread (see reference.py)
        self.t0 = time.perf_counter() if t0 is None else t0
        self.got = {}
        self.info = {}
        # per step of the loop: (step, data_s, step_s, record_s)
        self.phases = []
        self.trace_dir = None


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def drive(spec, cfg, traffic, plan: Plan, *, preset: str, rule,
          model_wrap=None):
    """``exp.run(spec)`` with the benchmark's weights, drawn by ``rule``,
    the configuration's overrides and the loop cut into segments; returns
    the run's ``Built`` and the weights' shapes. A leaf that ``rule`` does
    not place stops the run before its first compile."""
    from repro import exp
    from repro.core import driver
    from repro.models import build as build_model
    xbuild = importlib.import_module("repro.exp.build")

    seed = spec.run.seed
    orig_build, orig_loop = xbuild.build, driver.run_loop
    box = {}

    def build(s):
        built = orig_build(s)
        if cfg.get("overrides"):
            built.cfg = dataclasses.replace(built.cfg, **cfg["overrides"])
            built.model = build_model(built.cfg)
        if preset == "full":
            check_program_config(cfg, built.cfg)
        shapes = jax.eval_shape(lambda k: built.model.init(k, jnp.float32),
                                jax.random.key(0))
        rule.check(shapes)
        model = built.model._replace(
            init=lambda key, dtype=None: inputs.make_weights(shapes, seed,
                                                             rule))
        built.model = model_wrap(model) if model_wrap else model
        box.update(built=built, shapes=shapes)
        plan.info["built"] = time.perf_counter()
        return built

    def run_loop(step, state, *, steps, wps, period, start_step=0,
                 extra_fn=None, record=None, tracer=None, **_):
        hist, k = [], start_step
        now, cur = time.perf_counter, {}

        def data(j):
            a = now()
            out = extra_fn(j)
            cur["data"] = now() - a
            return out

        def timed_step(st, extra, t):
            a = now()
            out = step(st, extra, t)
            cur["step"] = now() - a
            return out

        def rec(j, t, st, out, dt):
            a = now()
            row = record(j, t, st, out, dt)
            plan.phases.append((j, cur["data"], cur["step"], now() - a))
            return row

        def seg(n):
            nonlocal state, k
            state, h = orig_loop(timed_step, state, steps=n, wps=wps,
                                 period=period, start_step=k, extra_fn=data,
                                 record=rec, tracer=tracer)
            hist.extend(h)
            k += n

        seg(1)
        plan.got["grad"] = leaf_norms(state.g_prev)
        if plan.spread:
            plan.got["spread"] = spread_norms(state.h)
        seg(reference.STEPS - 1)
        plan.got["change"] = change_norms(state.x, box["shapes"], seed, rule)
        plan.got["loss"] = [r["loss"] for r in hist[:reference.STEPS]]
        plan.info["first_ready"] = hist[0]["ready"]
        plan.info["checked"] = time.perf_counter()
        if plan.check_only:
            return state, hist
        # under a static plan dispatch each round phase is its own program
        cycle = period // math.gcd(wps, period)
        warm = max(4, cycle + 2 if traffic["gossip_impl"] == "auto" else 0)
        seg(warm)
        ready = [r["ready"] for r in hist[-warm:]]
        est = statistics.median(np.diff(ready).tolist())
        plan.info["step_estimate_s"] = est
        c0 = Compiles.count
        w0 = len(hist) - 1
        plan.info["window_start"] = hist[w0]["ready"]
        if plan.trace:
            plan.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(plan.trace_dir)
            seg(max(6, math.ceil(TRACE_SECONDS / est)))
            jax.profiler.stop_trace()
        else:
            seg(math.ceil(plan.seconds * 1.02 / est))
            while hist[-1]["ready"] - hist[w0]["ready"] < plan.seconds:
                seg(max(1, math.ceil(0.1 * plan.seconds / est)))
        plan.info["window_compiles"] = Compiles.count - c0
        plan.info["window"] = hist[w0:]
        return state, hist

    with patched(xbuild, "build", build), patched(driver, "run_loop", run_loop):
        exp.run(spec, quiet=True)  # the result holds the state: let it go
    return box["built"], box["shapes"]


def scenario(cfg, traffic, seed, preset="full"):
    """The realized scenario and the weights' shapes of a run, without
    running it (what the reference needs besides the seed)."""
    from repro import exp

    built = exp.build(make_spec(cfg, traffic, seed, preset))
    shapes = jax.eval_shape(lambda k: built.model.init(k, jnp.float32),
                            jax.random.key(0))
    return built, shapes


def stream_for(program_cfg, traffic, seed):
    """The reference's copy of the run's batches."""
    frames = program_cfg.encoder_seq if program_cfg.arch_type == "audio" else 0
    return inputs.Stream(seed=seed, nodes=traffic["nodes"], rounds=traffic["R"],
                         batch=traffic["batch"], seq=traffic["seq"],
                         vocab=program_cfg.vocab_size, frames=frames,
                         width=program_cfg.d_model)


def weights_fn(traffic, seed):
    """Round t's gossip weights for the reference, built from the traffic's
    topology and channel by ``topologies/<kind>.py`` and
    ``channels/<name>.py``: the graph's weights, less the links a channel
    loses (a link lost either way is lost), repaired, and held to the
    guarantee the schedule states."""
    n = traffic["nodes"]
    topo = traffic["topology"]
    graph = load_module(os.path.join(HERE, "topologies", topo["kind"] + ".py"),
                        "topology_" + topo["kind"].replace("-", "_"))
    channels = [(load_module(os.path.join(HERE, "channels", name + ".py"),
                             "channel_" + name), rate)
                for name, rate in sorted(traffic.get("channel", {}).items())
                if rate > 0]

    def at(t):
        w = graph.weights(n, t, seed, topo)
        if channels:
            keep = np.ones((n, n), bool)
            for mod, rate in channels:
                keep &= mod.survives(n, t, seed, rate)
            w = reference.repair(w, keep & keep.T)
        reference.check_doubly_stochastic(w)
        return w
    return at


def reference_readings(cell: Cell, program_cfg, shapes, seed, *, preset,
                       dtype=jnp.float32, precision="highest"):
    traffic = cell.traffic
    stream = stream_for(program_cfg, traffic, seed)
    x0 = inputs.make_weights(shapes, seed, cell.rule)
    return reference.run(cell.model.loss, cell.cfg_at(program_cfg, preset), x0,
                         stream.batch_at, weights_fn(traffic, seed),
                         nodes=traffic["nodes"], R=traffic["R"],
                         gamma=traffic["gamma"], dtype=dtype,
                         precision=precision)


def device_info():
    d = jax.devices()[0]
    stats = d.memory_stats() or {}
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": stats.get("peak_bytes_in_use", 0)}


def read_metric(name: str, facts: dict):
    mod = load_module(os.path.join(HERE, "metrics", name + ".py"),
                      "metric_" + name.replace(".", "_"))
    return mod.read(facts)


def metric_applies(m: dict, workload: str) -> bool:
    return "workloads" not in m or workload in m["workloads"]


def run_cell(bench: dict, workload: str, *, seed: int, seconds: float,
             trace: bool, t0: float, preset: str = "full",
             peaks: dict | None = None, model_wrap=None, root: str = HERE,
             log=lambda obj: print(json.dumps(obj), flush=True)) -> dict:
    """One run: set-up, window (or traced stretch), reference, comparison.
    The cell's files are found under ``root``. Returns the result object;
    earlier lines go through ``log``."""
    cell = find_cell(bench, workload, root)
    traffic, limits = cell.traffic, cell.limits
    Compiles.watch()
    plan = Plan(seconds=seconds, trace=trace, check_only=False, t0=t0,
                spread="spread_gap" in limits)
    spec = make_spec(cell.cfg, traffic, seed, preset)
    built, shapes = drive(spec, cell.cfg, traffic, plan, preset=preset,
                          rule=cell.rule, model_wrap=model_wrap)
    dev = device_info()
    window = plan.info["window"]
    ready = [r["ready"] for r in window]
    losses = [r["loss"] for r in window[1:]]
    cfg = cell.cfg_at(built.cfg, preset)
    facts = {"cfg": cfg, "traffic": traffic, "peaks": peaks,
             "setup_s": plan.info["window_start"] - t0, "ready": ready,
             "positions_per_step": counting.positions_per_step(cfg, traffic),
             "flops_per_step": counting.step_flops(cfg, traffic, cell.model),
             "memory_peak_bytes": dev["memory_peak_bytes"], "trace": None,
             "state_entries": sum(math.prod(s.shape) for s in
                                  jax.tree.leaves(shapes))}
    if trace:
        xplane = trace_reduce.find_xplane(plan.trace_dir)
        ev = trace_reduce.load(xplane)
        shutil.rmtree(plan.trace_dir, ignore_errors=True)
        facts["trace"] = trace_reduce.reduce(ev)
    gaps = np.diff(ready)
    # the steps of the window that took over 1.5 times the median, each
    # with the host's time in the loop's data, step and record calls
    steps = [r["step"] for r in window[1:]]
    phases = {p[0]: p[1:] for p in plan.phases}
    slow = [[k, float(g), *phases.get(k, ())] for k, g in zip(steps, gaps)
            if g > 1.5 * np.median(gaps)]
    log({"phase": "run", "workload": workload, "seed": seed,
         "steps_in_window": len(ready) - 1,
         "window_compiles": plan.info["window_compiles"],
         "step_estimate_s": plan.info["step_estimate_s"],
         "step_max_s": float(gaps.max()),
         "steps_over_1.5x_median": len(slow),
         "slow_steps": slow[:10],
         "median_phases_s": np.median(
             [phases[k] for k in steps if k in phases], axis=0).tolist(),
         "built_s": plan.info["built"] - t0,
         "first_step_ready_s": plan.info["first_ready"] - t0,
         "checked_s": plan.info["checked"] - t0,
         "check_losses": plan.got["loss"], "compiles": Compiles.count,
         "cache_hits": Compiles.hits, "cache_misses": Compiles.misses})

    gc.collect()
    r0 = time.perf_counter()
    ref = reference_readings(cell, built.cfg, shapes, seed, preset=preset)
    log({"phase": "reference", "seconds": time.perf_counter() - r0})
    read = reference.readings(plan.got, ref)
    checks = {k: {"value": read[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in checks.values())
    failed = sum(1 for v in losses if not math.isfinite(v))

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if not metric_applies(m, workload):
            continue
        v = read_metric(m["name"], facts)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if trace and facts["trace"] is not None:
        tr = facts["trace"]
        dev["busy_s"] = tr["busy_ns"] / 1e9
        dev["window_s"] = tr["window_ns"] / 1e9
    out = {"correct": bool(ok and failed == 0), "attempted": len(losses),
           "failed": failed, "metrics": metrics, "device": dev}
    if trace and facts["trace"] is not None:
        tr = facts["trace"]
        out["breakdown"] = {
            "device_ops": [[n, ns / 1e9] for n, ns in tr["top_ops"]],
            "idle_gaps": [[n, ns / 1e9] for n, ns in tr["idle_gaps"]]}
    out["checks"] = checks
    return out
