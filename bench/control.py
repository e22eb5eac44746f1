"""The control of the comparison that decides ``correct``.

A control is the plain reference put in the program's place with one of
the configuration's guarantees broken, the step a later change could be
tempted to take. Each traffic mix names its control (``"control"``):

- ``reads_skip_unflushed_writes``: reads see only the loaded records,
  not acknowledged inserts still in the MemTable (reads served from the
  device views alone).

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

runs the cell once per seed, in one process, on the chip, and prints per
seed the program's compared numbers and the control's, one JSON line
each. The benchmark's own runs never run it.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np


def _base_scan(ref, start: int, n: int):
    i0 = int(np.searchsorted(ref.keys, np.uint64(start)))
    return SimpleNamespace(keys=ref.keys[i0:i0 + n],
                           vals=ref.vals[i0:i0 + n])


def _base_get(ref, key: int):
    i = int(np.searchsorted(ref.keys, np.uint64(key)))
    found = i < len(ref.keys) and int(ref.keys[i]) == key
    return SimpleNamespace(found=found,
                           value=ref.vals[i] if found else None)


def reads_skip_unflushed_writes(ref):
    def answers(d):
        if d.req.kind == "scan":
            return [_base_scan(ref, d.req.key, d.req.n)]
        return [_base_get(ref, d.req.key)]

    return answers


CONTROLS = {f.__name__: f for f in (reads_skip_unflushed_writes,)}


def main(argv=None) -> int:
    import argparse

    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    # libtpu would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    from bench import harness

    if not harness.chips_ok(args.workload):
        return 2
    cell = harness.load_cell(args.workload)
    control = CONTROLS[cell["mix"]["control"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_process=t_process, control=control)
        print(json.dumps(dict(seed=seed, correct=out["correct"],
                              checked_ops=out["checked_ops"],
                              checks=out["checks"], control=out["control"],
                              metrics=out["metrics"])), flush=True)
        t_process = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
