"""The plain references agree with independent solvers and with the
program at a tiny size, import nothing of the program, and their
bfloat16 controls fail the limits."""
import ast
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.csgraph as csgraph

import graphs
from loader import HERE, load

CFG = {"graph": "delaunay", "vertices": 600, "graph_seed": 3,
       "root_seed": 4, "partitions": 4, "partitioner": "bfs_grow",
       "partition_seed": 5}
PR_PARAMS = {"num_iters": 30, "damping": 0.85}


@pytest.fixture(scope="module")
def ds():
    return graphs.dataset(CFG)


def test_references_import_nothing_of_the_program():
    for f in (HERE / "references").glob("*.py"):
        tree = ast.parse(f.read_text())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom) and n.module]
        assert not [m for m in names if m.split(".")[0] == "repro"], f


def test_sssp_reference_matches_scipy_dijkstra(ds):
    want = load("references", "sssp").reference(ds.n, ds.src, ds.dst, ds.w,
                                                ds.root, {})
    a = sp.csr_matrix((ds.w.astype(np.float64), (ds.src, ds.dst)),
                      shape=(ds.n, ds.n))
    d64 = csgraph.dijkstra(a, directed=False, indices=ds.root)
    assert np.array_equal(np.isfinite(want), np.isfinite(d64))
    fin = np.isfinite(d64)
    assert np.allclose(want[fin], d64[fin], rtol=1e-5, atol=0)


def test_pagerank_reference_matches_dense_power_iteration(ds):
    got = load("references", "pagerank").reference(ds.n, ds.src, ds.dst,
                                                   ds.w, ds.root, PR_PARAMS)
    adj = np.zeros((ds.n, ds.n))
    adj[ds.src, ds.dst] = adj[ds.dst, ds.src] = 1.0
    out = adj.sum(1)
    # column-stochastic transition with dangling columns sent uniformly
    m = np.where(out[None, :] > 0, adj.T / np.maximum(out, 1)[None, :],
                 1.0 / ds.n)
    r = np.full(ds.n, 1.0 / ds.n)
    for _ in range(PR_PARAMS["num_iters"]):
        r = 0.15 / ds.n + 0.85 * m @ r
    assert np.allclose(got, r, rtol=1e-12, atol=0)
    assert abs(got.sum() - 1.0) < 1e-12


@pytest.mark.parametrize("analytic", ["sssp", "pagerank"])
def test_program_agrees_with_reference_within_limits(ds, analytic):
    ref = load("references", analytic)
    params = PR_PARAMS if analytic == "pagerank" else {}
    view = graphs.build(ds, CFG, seed=2**31 + 11)
    out, _ = load("analytics", analytic).call(view, params, None)
    want = ref.reference(ds.n, ds.src, ds.dst, ds.w, ds.root, params)
    nums = ref.compare(view.to_canonical(out), want)
    assert all(v <= ref.LIMITS[k] for k, v in nums.items()), nums


def test_renumbering_keeps_shapes_and_the_roots_slot(ds):
    a = graphs.build(ds, CFG, seed=1)
    b = graphs.build(ds, CFG, seed=2**33 + 5)
    assert not np.array_equal(a.new_id, b.new_id)
    for f in ("nbr", "re_src", "vmask", "out_degree"):
        assert getattr(a.pg, f).shape == getattr(b.pg, f).shape
    assert a.pg.mailbox_cap == b.pg.mailbox_cap
    assign = a.pg.part_of[a.new_id]                 # canonical id -> part
    assert np.array_equal(assign, b.pg.part_of[b.new_id])
    r = ds.root
    rank = int(np.searchsorted(np.flatnonzero(assign == assign[r]), r))
    assert a.pg.local_of[a.new_id[r]] == b.pg.local_of[b.new_id[r]] == rank
    # the other vertices move: the program lays each partition out anew
    moved = a.pg.local_of[a.new_id] != b.pg.local_of[b.new_id]
    assert moved.mean() > 0.5


def test_dataset_is_a_delaunay_triangulation():
    """The edges are those of the lower convex hull of the points lifted
    onto the paraboloid z = x^2 + y^2, and they count 3n - 3 - h, h the
    hull's vertices."""
    from scipy.spatial import ConvexHull
    n, seed = 2000, 9
    src, dst, w = graphs.delaunay_edges(n, seed)
    pts = np.random.default_rng(seed).random((n, 2))
    lifted = ConvexHull(np.c_[pts, (pts**2).sum(1)])
    lower = lifted.simplices[lifted.equations[:, 2] < 0]
    e = np.concatenate([lower[:, [0, 1]], lower[:, [1, 2]], lower[:, [0, 2]]])
    e = np.unique(np.sort(e, axis=1), axis=0)
    assert np.array_equal(np.c_[src, dst], e)
    assert src.size == 3 * n - 3 - len(ConvexHull(pts).vertices)
    assert np.allclose(w, np.linalg.norm(pts[src] - pts[dst], axis=1),
                       rtol=1e-6)


def test_yardstick_files_stand_apart_from_the_program():
    """Only analytics/ (the program's entry), graphs.py (its GoFS build),
    control.py (which puts the control in the entry's place) and
    rehearse_compile.py may import the program."""
    allowed = {"analytics", "graphs.py", "rehearse_compile.py", "control.py",
               "tests"}
    for f in HERE.rglob("*.py"):
        rel = f.relative_to(HERE).parts[0]
        if rel in allowed:
            continue
        assert "repro" not in f.read_text(), f
    assert Path(HERE / "peaks.json").is_file()
