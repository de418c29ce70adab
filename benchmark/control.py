"""Read the comparison's numbers for several seeds in one process: of the
program as it stands, and of a control or a fault that has to come out
not correct.

    python3 benchmark/control.py --workload <name> --seconds <s> \
        --control none|reference_high|half_scan --seeds <n> [<n> ...]

``none`` runs the cell unchanged (the sound readings a limit is set
above).  ``reference_high`` puts the plain reference in the program's
place, computed one precision step below the configuration's float32 at
highest (``reference.exact_knn(precision="high")``).  The program's own
lower-precision switch (brute force's ``refine_precision="high"``) is no
control: its re-score is a float32 multiply-reduce that precision does
not reach, and it reads as the program does.  ``half_scan`` is a fault:
the program searches half of what the configuration states, brute force
the first half of the base and IVF-Flat half of its probes, and returns
exact distances of well-formed, wrong neighbours.

Each run is a whole run of the cell (set-up, a window of ``--seconds`` at
the cell's load, the comparison), and prints one JSON line with the
seed, ``correct`` and the checks.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def searcher_replaced(make):
    """Serve every request through ``make(index, k)``'s ``(fn, operands)``
    in place of the program's searcher."""
    import raft_tpu.serve.server as server_mod

    real = server_mod.make_searcher
    server_mod.make_searcher = lambda index, k, *a, **kw: make(index, k)
    try:
        yield
    finally:
        server_mod.make_searcher = real


def reference_searcher(precision: str):
    """The plain reference as a searcher over the raw base; for an index
    family the base is the one the cell's data step made."""
    import jax.numpy as jnp

    from benchmark import reference

    def make(base):
        def build(index, k):
            b = jnp.asarray(base, jnp.float32)
            b_sq = jnp.sum(b * b, axis=1)

            def fn(q, b, b_sq):
                return reference._knn_block(q, b, b_sq, k=k,
                                            precision=precision)
            return fn, (b, b_sq)
        return build
    return make


def half_scan(cell):
    """Plant the ``half_scan`` fault in ``cell``; returns the context to
    run it in."""
    if cell.config["family"] == "ivf_flat":
        cell.config["search"]["n_probes"] //= 2
        return contextlib.nullcontext()
    import raft_tpu.serve.server as server_mod

    real = server_mod.make_searcher

    def make(index, k, *a, **kw):
        return real(index[:index.shape[0] // 2], k, *a, **kw)
    return searcher_replaced(make)


def run_control(workload, seeds, seconds, control, root=ROOT,
                require_chip=True, cell_fn=None):
    """One line of readings per seed; ``cell_fn`` may shrink the cell."""
    from benchmark import harness, mixture, spec

    out = []
    for seed in seeds:
        cell = spec.load_cell(workload, root)
        if cell_fn is not None:
            cell = cell_fn(cell)
        ctx = contextlib.nullcontext()
        if control == "reference_high":
            # the reference replaces the searcher: the server is handed the
            # raw base, so no index is built for it
            cell.config["family"] = "brute_force"
            cell.config["search"] = {}
            base, _ = mixture.make(cell.config["data"],
                                   cell.config["mixture"], seed)
            ctx = searcher_replaced(reference_searcher("high")(base))
            del base
        elif control == "half_scan":
            ctx = half_scan(cell)
        with ctx:
            r = harness.run(workload, seed, seconds, False, root=root,
                            require_chip=require_chip, cell=cell)
        line = {"seed": seed, "control": control, "correct": r["correct"],
                "checks": r["checks"], "metrics": r["metrics"]}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", required=True,
                    choices=("none", "reference_high", "half_scan"))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[0] = ROOT
    run_control(args.workload, args.seeds, args.seconds, args.control)
    return 0


if __name__ == "__main__":
    sys.exit(main())
