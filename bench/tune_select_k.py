"""Offline select_k dispatch tuning — the reference's trained-heuristic
pattern (``cpp/scripts/heuristics/select_k/generate_heuristic.ipynb``:
time every algorithm over a (rows, cols, k) grid, bake the winner table
into the dispatcher).

Run on the target backend (real TPU for production numbers):

    python bench/tune_select_k.py [--quick]

Writes ``raft_tpu/matrix/_select_k_table.json``, keyed by
``rows.bit_length():cols.bit_length():k.bit_length()`` buckets;
``matrix.select_k``'s ``kAuto`` consults it at call time (absent entries
fall back to ``lax.top_k``).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from raft_tpu.core.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax

from _platform import pin_backend

# MUST precede any backend use (see _platform.py)
pin_backend(sys.argv)

import jax.numpy as jnp

from _timing import timeit as _time
from raft_tpu.matrix.select_k import SelectAlgo, select_k

GRID_ROWS = [256, 2048, 16384]
# 2048-wide / k=64 covers the brute-force fast path's shortlist cut
# ((m, 2·bn) → cand); the rest spans the select_k bench shapes
GRID_COLS = [1024, 2048, 16384, 131072]
GRID_K = [8, 32, 64, 128]
CANDIDATES = [SelectAlgo.kTopK, SelectAlgo.kPartialBitonic, SelectAlgo.kBinSelect]


def bucket_key(rows: int, cols: int, k: int) -> str:
    """The table/checkpoint bucket id — single home (the resume filter and
    the loop body must never desync on the key scheme)."""
    return f"{rows.bit_length()}:{cols.bit_length()}:{k.bit_length()}"


def kernel_sha() -> str:
    """Hash of the kernel + dispatch sources the table's timings depend
    on.  Recorded in the sidecar so "tuned against kernels that no longer
    exist" (the r3→r4 fori_loop staleness) is mechanically detectable, and
    used to scope the resume checkpoint."""
    import hashlib

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    h = hashlib.sha256()
    for rel in ("raft_tpu/ops/pallas/select_k.py",
                "raft_tpu/ops/bin_select.py",
                "raft_tpu/matrix/select_k.py",
                "raft_tpu/ops/blocked_scan.py",
                "raft_tpu/ops/pallas/gate.py"):
        with open(os.path.join(root, rel), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def main() -> None:
    quick = "--quick" in sys.argv
    rows_grid = [256, 2048] if quick else GRID_ROWS
    # quick mode keeps one short and one long column count (slicing the
    # grid would silently drop the long-row buckets that matter most)
    cols_grid = [1024, 16384] if quick else GRID_COLS
    sha = kernel_sha()
    backend = jax.default_backend()

    # resume checkpoint: the grid takes many fresh-compile minutes, so
    # every decided bucket is flushed immediately, and a re-run after a
    # kill skips buckets already decided under the SAME backend + kernel
    # sources
    ckpt_path = os.path.join(
        "/tmp", f"tune_select_k.{backend}.u{os.getuid()}.partial.json")
    table = {}
    try:
        with open(ckpt_path) as f:
            prior = json.load(f)
        if prior.get("backend") == backend and prior.get("kernel_sha") == sha:
            table = prior.get("table", {})
            print(f"resuming: {len(table)} buckets from checkpoint",
                  file=sys.stderr)
    except (OSError, ValueError):
        pass

    warned = []

    def flush_ckpt():
        try:
            with open(ckpt_path + ".tmp", "w") as f:
                json.dump({"backend": backend, "kernel_sha": sha,
                           "table": table}, f)
            os.replace(ckpt_path + ".tmp", ckpt_path)
        except OSError as e:
            # a silently-dead checkpoint would defeat the wedge-resume
            # feature exactly when it matters — warn once, keep tuning
            if not warned:
                warned.append(True)
                print(f"WARN: checkpoint flush failing ({e}); a mid-run "
                      f"kill will lose progress", file=sys.stderr)

    key0 = jax.random.PRNGKey(0)
    for rows in rows_grid:
        for cols in cols_grid:
            pending = [k for k in GRID_K
                       if k < cols and bucket_key(rows, cols, k) not in table]
            if not pending:
                continue
            x = jax.block_until_ready(
                jax.random.normal(key0, (rows, cols), jnp.float32))
            for k in pending:
                best_algo, best_t = None, float("inf")
                for algo in CANDIDATES:
                    if algo is SelectAlgo.kPartialBitonic and k > 64:
                        continue  # linear-in-k kernel: not competitive
                    try:
                        t = _time(lambda a=algo: select_k(x, k, algo=a))
                    except Exception as e:  # noqa: BLE001 — skip non-lowering algos
                        print(f"  {algo.name} rows={rows} cols={cols} k={k}: "
                              f"failed ({type(e).__name__})", file=sys.stderr)
                        continue
                    if t < best_t:
                        best_algo, best_t = algo, t
                if best_algo is None:
                    continue
                table[bucket_key(rows, cols, k)] = best_algo.value
                flush_ckpt()
                print(f"rows={rows:6d} cols={cols:7d} k={k:4d} → "
                      f"{best_algo.name} ({best_t * 1e3:.2f} ms)")
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "raft_tpu", "matrix", "_select_k_table.json")
    if jax.default_backend() != "tpu" and "--force" not in sys.argv:
        # an off-TPU run (CI smoke, contended-CPU drill) must never clobber
        # the production dispatch table the TPU search paths consult
        out = out.replace(".json", f".{jax.default_backend()}.json")
        print(f"non-TPU backend: writing to {os.path.basename(out)} "
              f"(--force overrides)", file=sys.stderr)
    with open(out, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    # provenance sidecar: NOT in the dispatch table (whose consumers —
    # dispatch, tests — treat every key as a b:l:k bucket)
    import datetime

    with open(out.replace(".json", ".meta.json"), "w") as f:
        json.dump({"backend": backend,
                   "date": datetime.date.today().isoformat(),
                   "kernel_sha": sha,
                   "n_entries": len(table)}, f)
        f.write("\n")
    try:
        os.remove(ckpt_path)  # spent: the final table supersedes it
    except OSError:
        pass
    print(f"wrote {len(table)} entries → {os.path.normpath(out)}")


if __name__ == "__main__":
    main()
