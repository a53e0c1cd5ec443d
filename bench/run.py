"""Run one cell of BENCHMARK.json once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``configs/``), a traffic mix
(``traffic/<name>.json``, whose ``kind`` names the runner in ``harness/``)
and its limits (``limits/<cell>.json``).  With ``--trace 0`` the result
carries the cell's end-to-end metrics; with ``--trace 1`` a profiled part
of the window gives its per-layer metrics (``metrics/<name>.py``), the
device's busy time and a breakdown.  The last line of standard output is
one JSON object; the numbers that decide ``correct`` are printed beside
their limits as the last lines of standard error and, under ``checks``,
last in that object.

It runs on the chips of the machine it is started on and refuses to run
anywhere else: with no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import cell as cells  # noqa: E402
from harness import compare, device, tracing  # noqa: E402


def _load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a runner needs from the harness: the start time, the chips,
    the profiler, and the assembly of the result line."""

    def __init__(self, devs, t_start: float = T_START):
        self.devs = devs
        self.t_start = t_start

    def memory_peak(self) -> int:
        return device.memory_peak(self.devs)

    @contextlib.contextmanager
    def quiet(self):
        """The measured window, with the garbage collector off (what set-up
        left is frozen out of later collections).  Any compilation inside
        it is logged, and what the process waited for is printed after."""
        import jax
        gc.collect()
        gc.freeze()
        gc.disable()
        jax.config.update("jax_log_compiles", True)
        r0, t0 = resource.getrusage(resource.RUSAGE_SELF), time.perf_counter()
        try:
            yield
        finally:
            r1, t1 = resource.getrusage(resource.RUSAGE_SELF), \
                time.perf_counter()
            jax.config.update("jax_log_compiles", False)
            gc.enable()
            gc.unfreeze()
            print(f"window host: {t1 - t0:.3f} s wall, "
                  f"{r1.ru_utime - r0.ru_utime:.3f} s user, "
                  f"{r1.ru_stime - r0.ru_stime:.3f} s system, "
                  f"{r1.ru_majflt - r0.ru_majflt} major faults, "
                  f"{r1.ru_minflt - r0.ru_minflt} minor faults, "
                  f"{r1.ru_nivcsw - r0.ru_nivcsw} involuntary and "
                  f"{r1.ru_nvcsw - r0.ru_nvcsw} voluntary switches",
                  file=sys.stderr, flush=True)

    def say_intervals(self, what: str, intervals) -> None:
        """Print the quartiles and the longest of the window's ``what``
        times, where the longest fell, and how many took over 1.5 times
        the median, to standard error."""
        import numpy as np
        x = np.asarray(intervals, np.float64)
        if x.size:
            i = int(x.argmax())
            q1, q2, q3 = np.percentile(x, [25, 50, 75])
            print(f"window {what}s: {x.size}, quartiles {q1:.6f} {q2:.6f} "
                  f"{q3:.6f} s, longest {x[i]:.6f} s at {i} "
                  f"({float(x[:i].sum()):.3f} s in), "
                  f"{int((x > 1.5 * q2).sum())} over 1.5x the median",
                  file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def traced(self):
        """Profile the enclosed region; ``.trace`` holds its reduction."""
        out = types.SimpleNamespace(trace=None)
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            with tracing.capture(log_dir):
                yield out
            out.trace = tracing.reduce(tracing.find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    def result(self, cell, numbers, *, attempted, failed, e2e, counters,
               spans, trace, memory) -> dict:
        correct, checks = compare.judge(numbers, cell.limits)
        dev = device.describe()
        dev["memory_peak_bytes"] = memory
        out = {"correct": correct, "attempted": int(attempted),
               "failed": int(failed)}
        if trace is None:
            out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
                              for m in cell.end_to_end}
        else:
            reading = types.SimpleNamespace(
                cell=cell, trace=trace, spans=spans, counters=counters,
                chips=len(self.devs), peaks=device.peaks(dev["kind"]))
            metrics = {}
            for m in cell.per_layer:
                value = _load_reader(m["name"])(reading)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            out["metrics"] = metrics
            dev["busy_s"] = trace.busy_s
            dev["window_s"] = trace.window_s
        out["device"] = dev
        if trace is not None:
            out["breakdown"] = trace.breakdown()
        out["checks"] = checks
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(cells.ROOT, "src")
    sys.path.insert(0, src)
    try:
        from repro import compile_cache
    except ImportError:
        print(f"the system under test is not here: {src} holds no repro "
              f"package", file=sys.stderr)
        return 2
    cell = cells.load(args.workload)
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = device.require(cell.chips)
    runner = importlib.import_module("harness." + cell.traffic["kind"])
    res = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     Context(devs))
    compare.print_checks(res["checks"])
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
