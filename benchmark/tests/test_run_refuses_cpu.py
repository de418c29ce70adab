"""``run.py`` measures on the accelerator or not at all: with only the
CPU it prints no result and exits with a code other than 0."""

import os
import subprocess
import sys

from benchmark import spec


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
         "--workload", "sift1m-ivf_flat.batch", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_warm_buckets_follow_the_traffic():
    from benchmark import harness

    ladder = (1, 8, 64, 512)
    assert harness.warm_buckets(ladder, {"loop": "closed", "clients": 4,
                                         "rows_per_request": 512}) == [512]
    assert harness.warm_buckets(ladder, {"loop": "closed", "clients": 64,
                                         "rows_per_request": 1}) == [1, 8, 64]
    assert harness.warm_buckets(ladder, {"loop": "closed", "clients": 2,
                                         "rows_per_request": 3}) == [8]
