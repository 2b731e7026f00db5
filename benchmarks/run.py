#!/usr/bin/env python3
"""One process, one cell, one run.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data that this file finds by the
names in ``BENCHMARK.json``: the configuration's file (``configs``), the
traffic mix (``benchmarks/traffic/<traffic>.json``, which names its
driver), the limits of the check of outputs (``benchmarks/limits/<cell>.json``),
and for every per-layer metric ``benchmarks/metrics/<metric>.json``, which
names its reader (``benchmarks/readers/<reader>.py``).  This file holds
no cell's, configuration's or metric's name.

The run: require the TPU and the cell's chips (else exit 2, no result
line), place the compile cache, set up and warm the cell's own shapes
(``setup_s``: everything from this file's first line to the window's
start but the one call that claims the chips, whose length is the
machine's: ``backend_start_s`` in the observation line), measure for
``--seconds`` with compilations counted (one inside the window: exit 3,
no result line), read the peak memory, free the program's state, run the
plain reference over what the timed path produced, print every number
compared beside its limit (standard error, and last in the result
line), print the result line.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # as near to process start as Python lets us

import argparse
import importlib.util
import json
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_NO_CHIP, EXIT_COMPILED, EXIT_BAD_CELL = 2, 3, 4


class Refused(Exception):
    """The run cannot be a measurement; carries the exit code."""

    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


def say(**fields) -> None:
    """An observation line on standard output, never the last one."""
    print(json.dumps(fields), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` by path (names may hold ``-``)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise Refused(EXIT_BAD_CELL, f"no {kind}/{name}.py")
    mod_name = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> types.SimpleNamespace:
    """The cell's entry of ``BENCHMARK.json`` with its files read in."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(EXIT_BAD_CELL, f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    per_layer = [
        m for m in bench["per_layer"]
        if "workloads" not in m or name in m["workloads"]
    ]
    end_to_end = [
        m for m in bench["end_to_end"]
        if "workloads" not in m or name in m["workloads"]
    ]
    return types.SimpleNamespace(
        name=name,
        chips=int(cell["chips"]),
        config_name=cell["config"],
        config=load_json(root, conf["file"]),
        traffic_name=cell["traffic"],
        traffic=load_json(HERE, "traffic", f"{cell['traffic']}.json"),
        limits={k: v for k, v in
                load_json(HERE, "limits", f"{name}.json").items()
                if not k.startswith("_")},
        end_to_end={m["name"]: m for m in end_to_end},
        per_layer={
            m["name"]: dict(load_json(HERE, "metrics", f"{m['name']}.json"),
                            **m)
            for m in per_layer
        },
    )


class CompileCount:
    """Programs built or fetched from the cache since ``reset``: jax's own
    monitoring events, so a program the window needed and set-up did not
    warm shows whether it compiled or came from the persistent cache."""

    EVENTS = ("backend_compile", "cache_retrieval_time")

    def __init__(self, jax):
        self.n = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if any(e in event for e in self.EVENTS):
            self.n += 1
            self.names.append(event)

    def reset(self):
        self.n, self.names = 0, []


class Tracer:
    """Starts and stops the profiler around the driver's steady stretch
    and reduces what it wrote.  The trace lives in a fixed directory
    inside the checkout and is removed once read."""

    def __init__(self, jax, out_dir: str, fixture: str | None = None):
        self.jax, self.dir, self.trace = jax, out_dir, None
        # the tests' recorded trace: the CPU's profile has no device plane
        self.fixture = fixture

    def start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host python frames: large, unread
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        self.jax.profiler.stop_trace()

    def reduce(self):
        import trace_reduce

        try:
            self.trace = trace_reduce.reduce(self.fixture or self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace


class MemoryWatch:
    """The peak of device memory on the fullest chip.  This runtime keeps
    the compiled programs' own buffers (a step's temporaries) apart from
    the allocator's bytes in use, as bytes reserved, so what a chip holds
    at a moment is the sum of the two.  Their two peaks need not fall
    together (a model's start-up state may be freed before the large
    programs load), so the sum is sampled — after set-up, by the driver
    inside the window if it likes, after the window — and the peak
    reported is the largest sample, or the allocator's own peak in use
    where that is larger."""

    def __init__(self, devices):
        self.devices, self.peak = devices, 0

    def sample(self) -> int:
        for d in self.devices:
            st = d.memory_stats() or {}
            now = int(st.get("bytes_in_use", 0)) + int(st.get("bytes_reserved", 0))
            self.peak = max(self.peak, now, int(st.get("peak_bytes_in_use", 0)))
        return self.peak


def device_report(devices, memory: MemoryWatch) -> dict:
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices), memory_peak_bytes=memory.sample())


def apply_rehearsal(cell, rehearsal: dict) -> None:
    """The tests' tiny sizes: overrides of the configuration, the traffic
    and the limits, given as an argument and by nothing the chip run
    could inherit."""
    for part in ("config", "traffic", "limits"):
        over = rehearsal.get(part, {})
        target = getattr(cell, part)
        for k, v in over.items():
            if isinstance(v, dict) and isinstance(target.get(k), dict):
                target[k] = {**target[k], **v}
            else:
                target[k] = v


def make_context(cell, seed: int, rehearsal: dict | None,
                 need_chips: bool = True):
    """Checks the chip, places the cache and gathers what a driver needs.
    Returns (ctx, driver module, jax, compile counter, cache dir).
    ``need_chips=False`` is for readings of the reference alone, which
    run on one chip whatever the cell spans (``calibrate.py``)."""
    driver_mod = load_module("drivers", cell.traffic["driver"])

    import jax

    # the first call claims the chips: the runtime's start-up, which is
    # the machine's and no work of the program or of the benchmark
    t_claim = time.perf_counter()
    devices = jax.devices()
    backend_start_s = time.perf_counter() - t_claim
    if rehearsal is None:
        if devices[0].platform != "tpu":
            raise Refused(
                EXIT_NO_CHIP,
                f"jax found platform {devices[0].platform!r}, not a TPU",
            )
        if need_chips and len(devices) < cell.chips:
            raise Refused(
                EXIT_NO_CHIP,
                f"cell needs {cell.chips} chips, jax found {len(devices)}",
            )
    devices = devices[: cell.chips]
    peaks_table = load_json(HERE, "peaks.json")
    kind = devices[0].device_kind
    if rehearsal is not None:
        # sizes for the arithmetic only; nothing from a rehearsal is a
        # device number and its result line names the CPU
        peaks = next(v for k, v in peaks_table.items() if not k.startswith("_"))
    elif kind not in peaks_table:
        raise Refused(EXIT_NO_CHIP, f"device kind {kind!r} is not in peaks.json")
    else:
        peaks = peaks_table[kind]

    try:
        from theanompi_tpu import cachedir
    except ImportError as e:
        raise Refused(EXIT_BAD_CELL, f"the program is not in this checkout: {e}")

    cache = cachedir.configure_compile_cache(jax)
    # every program of a cell, the small ones too, comes from the cache
    # after the first run there (thresholds only: the directory is the
    # program's rule, or JAX_COMPILATION_CACHE_DIR's)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileCount(jax)
    ctx = types.SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic, chips=cell.chips,
        seed=int(seed), devices=devices, peaks=peaks,
        rehearsal=rehearsal is not None,
        reference=load_module("references", cell.config_name),
        flops=load_module("flops", cell.config_name),
        say=say, t0=_T0, memory=MemoryWatch(devices),
        backend_start_s=backend_start_s,
    )
    return ctx, driver_mod, jax, _COMPILES, cache


_COMPILES = None


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: dict | None = None, root: str = ROOT):
    """Returns (result line as a dict, rows compared)."""
    cell = load_cell(workload, root)
    if rehearsal is not None:
        apply_rehearsal(cell, rehearsal)
    ctx, driver_mod, jax, compiles, cache = make_context(cell, seed, rehearsal)
    compiles.reset()
    driver = driver_mod.Driver(ctx)
    driver.setup()
    setup_programs = compiles.n
    tracer = None
    if trace:
        tracer = Tracer(jax, os.path.join(root, ".bench_out", "trace", workload),
                        fixture=(rehearsal or {}).get("trace_fixture"))

    compiles.reset()
    ctx.memory.sample()
    since_start_s = time.perf_counter() - _T0
    setup_s = since_start_s - ctx.backend_start_s
    driver.window(float(seconds), tracer)
    in_window = compiles.n
    if in_window:
        raise Refused(
            EXIT_COMPILED,
            f"{in_window} program(s) built inside the window: {compiles.names[:5]}",
        )
    device = device_report(ctx.devices, ctx.memory)
    say(memory_stats={k: v for k, v in (ctx.devices[0].memory_stats() or {}).items()
                      if isinstance(v, (int, float))})
    end_to_end = dict(driver.end_to_end_values(), setup_s=setup_s)
    facts = driver.facts()
    attempted, failed = driver.attempted_failed()
    reduced = tracer.reduce() if tracer is not None else None

    driver.release()
    t_check = time.perf_counter()
    numbers = driver.check()
    check_s = time.perf_counter() - t_check

    import compare

    rows = compare.judge(numbers, cell.limits)
    correct = all(ok for *_, ok in rows)

    units = {k: m["unit"] for k, m in cell.end_to_end.items()}
    if trace:
        metrics = {}
        for name, m in cell.per_layer.items():
            reader = load_module("readers", m["reader"])
            value = reader.read(types.SimpleNamespace(
                facts=facts, trace=reduced, args=m.get("args", {}),
                peaks=ctx.peaks, flops=ctx.flops, config=cell.config,
                chips=cell.chips,
            ))
            if value is not None:
                metrics[name] = dict(value=float(value), unit=m["unit"])
        # every operation's total, for whoever writes the next reader
        os.makedirs(os.path.join(root, ".bench_out"), exist_ok=True)
        with open(os.path.join(root, ".bench_out", f"ops-{workload}.json"), "w") as f:
            json.dump(sorted(reduced.op_totals().items(),
                             key=lambda kv: -kv[1])[:400], f)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    else:
        missing = [k for k in units if k not in end_to_end]
        if missing:
            raise Refused(EXIT_BAD_CELL, f"driver reported no {missing}")
        metrics = {k: dict(value=float(end_to_end[k]), unit=units[k])
                   for k in units}
    say(workload=workload, seed=int(seed), seconds=seconds, trace=int(trace),
        cache=cache, setup_programs=setup_programs, check_s=check_s,
        backend_start_s=ctx.backend_start_s, since_start_s=since_start_s,
        end_to_end=end_to_end, where=numbers.get("_where"))
    result = dict(correct=bool(correct), attempted=int(attempted),
                  failed=int(failed), metrics=metrics, device=device)
    if reduced is not None:
        result["breakdown"] = reduced.breakdown()
    result["compared"] = {
        name: dict(value=value, limit=limit) for name, value, limit, _ in rows
    }
    return result, rows


def main(argv=None, rehearsal: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, rows = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), rehearsal=rehearsal)
    except Refused as e:
        print(f"run.py: no measurement: {e}", file=sys.stderr, flush=True)
        return e.code
    for name, value, limit, ok in rows:
        print(f"compared {name} = {value} limit {limit} "
              f"{'ok' if ok else 'NOT CORRECT'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
