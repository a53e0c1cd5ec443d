"""Readings that the limits of a cell are set from (on the chip).

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 20] [--rounds N]

In one process, for each of ``--seeds``: the program's numbers, as a run
of ``run.py`` computes them (set-up through the window's own calls, then
the plain reference at float32).  For each of ``--control-seeds``: the
same numbers with each control put in the program's place (the reference
with int8 operands, and with bfloat16 storage: ``harness/reference.py``),
and for training cells also with half of every batch left out, the mean
taken over the rest (a planted fault).  The lower reading of a number is
the largest the program gives; the upper is the smallest the control or a
fault gives.

``--rounds N`` (training cells) instead follows the loss over N rounds,
the program's and the plain reference's, from each of ``--seeds``.

Each reading is one JSON line on standard output.  The benchmark's own
runs never run this.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import cell as cells  # noqa: E402
from harness import compare, device  # noqa: E402


def _say(**kw) -> None:
    print(json.dumps(kw), flush=True)


CONTROLS = ("int8", "bf16")


def _judged(cell, seed: int, who: str, numbers: dict, **extra) -> None:
    """One reading, with the verdict the cell's limits give it."""
    correct, _ = compare.judge(numbers, cell.limits)
    _say(seed=seed, who=who, correct=correct, **numbers, **extra)


def train(cell, seeds, control_seeds) -> None:
    from harness import train_rounds as T

    for seed in sorted(set(seeds) | set(control_seeds)):
        key, _ = cells.seed_streams(seed)
        t = time.perf_counter()
        ref = T.reference_obs(cell, key)
        t_ref = time.perf_counter() - t
        if seed in seeds:
            prog = T.Program(cell, key)
            obs = prog.warmup()
            prog.free()
            del prog
            _judged(cell, seed, "program", T.numbers(obs, ref), ref_s=t_ref)
        if seed in control_seeds:
            for who, kw in ([("control_" + c, {"low": c}) for c in CONTROLS]
                            + [("fault_half_batch", {"half_batch": True})]):
                _judged(cell, seed, who,
                        T.numbers(T.reference_obs(cell, key, **kw), ref))


def _by_round(losses, h: int) -> dict:
    import numpy as np
    x = np.asarray(losses, np.float64).reshape(-1, h)
    bad = np.flatnonzero(~np.isfinite(x.reshape(-1)))
    return {"round_loss": [float(v) for v in x.mean(axis=1)],
            "first_nonfinite_step": int(bad[0]) if bad.size else None}


def train_rounds(cell, seeds, rounds: int) -> None:
    import jax
    from harness import train_rounds as T

    h = cell.traffic["h"]
    for seed in seeds:
        key, _ = cells.seed_streams(seed)
        prog = T.Program(cell, key)
        losses = []
        for _ in range(rounds):
            losses += [prog.inner_step() for _ in range(h)]
            prog.outer_step()
        losses = jax.device_get(losses)
        prog.free()
        del prog
        _say(seed=seed, who="program", **_by_round(losses, h))
        t = time.perf_counter()
        ref = T.reference_rounds(cell, key, rounds)
        _say(seed=seed, who="reference", ref_s=time.perf_counter() - t,
             **_by_round(ref["losses"], h))


def serve(cell, seeds, control_seeds, seconds) -> None:
    from harness import serve_closed_loop as S

    tr, m = cell.traffic, cell.model
    for seed in sorted(set(seeds) | set(control_seeds)):
        key, rng = cells.seed_streams(seed)
        prog = S.Program(cell, key)
        prog.start(S.request_list(tr, m["vocab_size"], rng))
        prog.window(seconds=seconds)
        checked = S.sample(prog.finish(tr["check_requests"]),
                           tr["check_requests"], rng)
        prog.free()
        del prog
        rows, where = S.served(checked, S.max_len(tr))
        whos = ([("program", "")] if seed in seeds else []) + (
            [("control_" + c, c) for c in CONTROLS]
            if seed in control_seeds else [])
        for who, low in whos:
            gaps = S.logit_gaps(cell, key, rows, where, low=low)
            _judged(cell, seed, who, {"logit_gap": float(gaps.max())},
                    tokens=len(gaps))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rounds", type=int, default=0)
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    sys.path.insert(0, os.path.join(cells.ROOT, "src"))
    from repro import compile_cache
    cell = cells.load(args.workload)
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device.require(cell.chips)
    if args.rounds:
        train_rounds(cell, ints(args.seeds), args.rounds)
    elif cell.traffic["kind"] == "train_rounds":
        train(cell, ints(args.seeds), ints(args.control_seeds))
    else:
        serve(cell, ints(args.seeds), ints(args.control_seeds), args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
