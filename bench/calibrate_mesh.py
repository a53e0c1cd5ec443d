"""``calibrate.py`` for cells of kind ``train_rounds_mesh``, whose plain
reference is spread over the cell's chips (on the chip).

    python bench/calibrate_mesh.py --workload <cell> --seeds 1,2 \
        [--control-seeds 1,2]

For each of ``--seeds``: the program's numbers, as a run of ``run.py``
computes them.  For each of ``--control-seeds``: the same numbers with the
reference at int8 operands and at bfloat16 storage in the program's place,
with half of every batch left out, and with an outer step that averages
cluster 0's pseudo-gradient alone (nothing crosses between clusters).  The
control seeds go first.  One JSON line per reading.  The
benchmark's own runs never run this.
"""
import argparse
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from calibrate import CONTROLS, _judged  # noqa: E402
from harness import cell as cells  # noqa: E402
from harness import device  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    seeds, control_seeds = ints(args.seeds), ints(args.control_seeds)
    sys.path.insert(0, os.path.join(cells.ROOT, "src"))
    from repro import compile_cache
    cell = cells.load(args.workload)
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = device.require(cell.chips)
    from harness import train_rounds as T
    from harness import train_rounds_mesh as R

    for seed in dict.fromkeys(control_seeds + seeds):
        key, _ = cells.seed_streams(seed)
        obs = None
        if seed in seeds:
            prog = T.Program(cell, key)
            obs = prog.warmup()
            prog.free()
            del prog
        t = time.perf_counter()
        ref = R.reference_obs(cell, key, devs)
        t_ref = time.perf_counter() - t
        if obs is not None:
            _judged(cell, seed, "program", T.numbers(obs, ref), ref_s=t_ref)
        if seed in control_seeds:
            for who, kw in ([("control_" + c, {"low": c}) for c in CONTROLS]
                            + [("fault_half_batch", {"half_batch": True}),
                               ("fault_no_exchange", {"no_exchange": True})]):
                _judged(cell, seed, who, T.numbers(
                    R.reference_obs(cell, key, devs, **kw), ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
