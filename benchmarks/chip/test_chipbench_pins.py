"""``google1024.light`` reads the same inputs as before its files could
state a fleet and a job mix: the digests, prices and capacity array
below were recorded on the generator and harness as they stood then
(one capacity for every machine, the google preset's draws hard-coded),
and every later harness must reproduce them bit for bit (CPU only).

A backlog digest is the SHA-256 of ``repr((job, fail_at))`` for each of
the backlog's first 2,000 jobs in order, encoded as UTF-8: the
program's ``JobSpec`` with every field, its demand maps in their order,
and the failure slot. Prices are compared by ``float.hex``."""
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(REPO / "src"))

from gen.traffic import backlog  # noqa: E402
from harness import cells, engine as eng  # noqa: E402

CELL = "google1024.light"
DIGESTS = {
    1: "2e05cd33bd7674625b5030ca9dda89de8d00da260e0c23e65f221804341d111d",
    2**31 + 5: "2592a8ed0369e3ca3e6342515321fa7b7644fe4574fc06755247b22c0022aac2",
    4_000_000_017: "7c92522bddd69e05dc101cf593070dac3f942f031a4631fd04501d1bc5468be3",
}
PRICES_U = {"cpu": "0x1.38456fe1e51f8p+4", "gpu": "0x1.3cc57cc95dce7p+6",
            "mem": "0x1.7a62c688051c8p+2", "storage": "0x1.98e121f0115c7p+2"}
PRICES_L = "0x1.12e0be826d695p-30"
PRICES_MU = "0x1.0cccccccccccdp+17"
CAP_SHA = "5e5fc10f08ea1def42cbf280973b48980dd689bc1307a953a9500a110562a895"


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(cells.load_benchmark(REPO), CELL, REPO)


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_backlog_digest_is_pinned(cell, seed):
    h = hashlib.sha256()
    it = backlog(cell.traffic, seed)
    for _ in range(2000):
        h.update(repr(next(it)).encode())
    assert h.hexdigest() == DIGESTS[seed]


def test_prices_and_capacities_are_pinned(cell):
    """The engine's own build: its calibrated prices, the program's
    cluster, and the reference's capacity array."""
    from repro.core.cluster import make_cluster
    cfg = cell.config
    run = eng.build(cfg, cell.traffic, 1, "numpy", float("inf"))
    p = run.prices
    assert {r: u.hex() for r, u in p.U.items()} == PRICES_U
    assert (p.L.hex(), p.mu.hex()) == (PRICES_L, PRICES_MU)

    cap = cfg.capacity_array()
    assert cap.shape == (1024, 4) and cap.dtype == np.float64
    assert hashlib.sha256(cap.tobytes()).hexdigest() == CAP_SHA
    assert cfg.resources == ["cpu", "gpu", "mem", "storage"]
    np.testing.assert_array_equal(cap[0], [180.0, 72.0, 576.0, 180.0])

    cluster = run.engine.window.cluster
    want = make_cluster(1024, 64, preset="ethernet", backend="numpy")
    assert cluster.resources == want.resources
    np.testing.assert_array_equal(cluster.capacity_matrix, want.capacity_matrix)
    assert cluster.total_capacity() == want.total_capacity()
    assert cluster.capacity_matrix.tobytes() == cap.tobytes()
