"""CPU tests of the ``dse`` traffic kind and of how the harness finds a
kind's driver: the configuration's spelled-out grid against the
simulator's stock grid, a fault in a checked point, and the four-chip
placement on four virtual CPU devices.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_dse.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench.kinds import dse as kind
from bench.lib import drivers, harness
from bench.lib.harness import ROOT
from bench.tests import cells

SEED = 2**31 + 12345
CELL = "dse_grid.default"


def test_grid_is_the_stock_grid():
    from repro.core.noc import dse
    from repro.core.noc.spec import FabricSpec

    config = harness.load_cell(CELL)[2]
    specs = [FabricSpec(**p) for _, p in kind.grid(config)]
    assert specs == dse.default_grid()
    assert len(specs) == 136 and len(config["grid"]) == len(dse.build_jobs(specs))


def test_kind_is_found_as_a_file():
    assert harness.driver_class("dse").__name__ == "Driver"
    assert harness.driver_class("sweep") is drivers.SweepDriver
    with pytest.raises(KeyError, match=r"'no_such_kind'.*bench.kinds|kinds.*'no_such_kind'"):
        harness.driver_class("no_such_kind")


def test_fault_in_a_checked_point_is_caught():
    loaded = cells.small(CELL)
    point = kind.checked_points(loaded[2], SEED, loaded[3]["checked_per_group"])[0]
    with cells.fault("point", point):
        out = cells.run(loaded, SEED, require_tpu=False)
    assert not out["correct"], cells.dumps(out)
    assert out["checks"]["state_mismatch"]["value"] > 0


FOUR = """
import json
from bench.tests import cells
loaded = cells.small("dse_grid.default")
loaded[2]["grid"] = [dict(g, patterns=g["patterns"][:1]) for g in
                     cells.harness.load_cell("dse_grid.default")[2]["grid"]]
out = {"sound": cells.run(loaded, %d, require_tpu=False)}
with cells.fault("one_device"):
    out["one_device"] = cells.run(loaded, %d, require_tpu=False)
print(json.dumps({k: [v["correct"], v["checks"]] for k, v in out.items()}))
"""


def test_four_devices_take_the_groups_round_robin():
    """All six fabrics of the grid, one point each, on four virtual CPU
    devices: groups 0-5 land on devices 0, 1, 2, 3, 0, 1; a pass that puts
    every group on one device is not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", FOUR % (SEED, SEED)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"][0], out["sound"][1]
    assert not out["one_device"][0]
    assert out["one_device"][1]["placement_mismatch"]["value"] > 0
