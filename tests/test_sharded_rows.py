"""Features held with their rows over a four-device partition.

The sharded checked forward keeps each row of H with its stripe: the tile
table is planned from the COO and built and staged a shard at a time, the
combination runs on each device's rows, and only each layer's X and x_r
are gathered.  These tests hold it to a plain numpy forward, to the
one-host tile table bit for bit, and to the fault hook: an upset injected
at a global stripe flags on whichever shard owns it, names that stripe,
and leaves every other shard's rows as the clean run left them.

The device count is fixed when JAX starts, so the sharded cases run in a
child process with ``--xla_force_host_platform_device_count=4`` (as in
``test_sharded_engine.py``); the layout cases run here.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.kernels.spmm_abft.layout import (coo_to_block_ell, pad_block_rows,
                                            plan_block_ell)

SHARDS = 4
N, F, BLOCK = 300, 40, 16
DIMS = (F, 16, 7)


def _graph(seed=0):
    """A random symmetric graph with self loops, sym-normalized, as COO."""
    rng = np.random.default_rng(seed)
    e = rng.integers(0, N, (700, 2))
    e = e[e[:, 0] != e[:, 1]]
    src = np.concatenate([e[:, 0], e[:, 1], np.arange(N)])
    dst = np.concatenate([e[:, 1], e[:, 0], np.arange(N)])
    deg = np.bincount(src, minlength=N).astype(np.float64)
    val = (1.0 / np.sqrt(deg[src] * deg[dst])).astype(np.float32)
    h0 = rng.random((N, F)).astype(np.float32)
    ws = [rng.normal(0, 0.3, (a, b)).astype(np.float32)
          for a, b in zip(DIMS[:-1], DIMS[1:])]
    return src, dst, val, h0, ws


def _numpy_forward(src, dst, val, h0, ws):
    """S relu(S H W1) W2 in float32 numpy: no kernel, no engine."""
    s = np.zeros((N, N), np.float32)
    np.add.at(s, (src, dst), val)
    return s @ (np.maximum(s @ (h0 @ ws[0]), 0.0) @ ws[1])


# -- the child process --------------------------------------------------------

def _spans(tdir):
    import glob

    import jax
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, dict(e.stats)) for plane in data.planes
                   if not plane.name.startswith("/device")
                   for line in plane.lines for e in line.events
                   if e.name in ("gcn.stage", "gcn.exchange")),
                  key=lambda s: (s[0], s[1].get("shard", -1),
                                 s[1].get("layer", -1)))


def sharded_cases() -> dict:
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.core.abft import ABFTConfig
    from repro.engine import (Graph, Partition, fold_w_r, gcn_apply,
                              gcn_forward, make_backend, stage_rows)
    from repro.launch.mesh import make_graph_mesh

    src, dst, val, h0, ws = _graph()
    part = Partition(make_graph_mesh(SHARDS), "graph")
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)
    params = fold_w_r({"layers": [{"w": jnp.asarray(w)} for w in ws]}, cfg)
    plan = plan_block_ell(src, dst, val, (N, N), BLOCK, BLOCK)
    out = {"devices": len(jax.devices())}

    def rows(lo, hi):
        block = np.zeros((hi - lo, F), np.float32)
        top = min(hi, N)
        if top > lo:
            block[:top - lo] = h0[lo:top]
        return block

    def make(**kw):
        return make_backend(plan, cfg, partition=part, block_g=16,
                            interpret=True, **kw)

    with tempfile.TemporaryDirectory() as tdir:
        with jax.profiler.trace(tdir):
            bk = make()
            padded = bk.vals.shape[0] * BLOCK
            held = stage_rows(part, padded, rows)
            graph = Graph(s=plan, h0=held)
            logits, report = gcn_apply(params, graph, cfg, backend=bk)
            logits.block_until_ready()
        out["spans"] = _spans(tdir)
    clean = np.asarray(logits)
    ref = _numpy_forward(src, dst, val, h0, ws)
    out.update(
        padded_rows=padded,
        logits_spec=str(logits.sharding.spec),
        logit_gap=float(np.abs(clean[:N] - ref).max() / np.abs(ref).max()),
        padding_max=float(np.abs(clean[N:]).max()),
        clean_flag=bool(report.flag),
        staged_bytes={str(k): v for k, v in bk.staged_bytes.items()},
        device_ids=[d.id for d in part.mesh.devices.flat],
    )

    # the staged table, gathered back, against the one-host conversion
    table = pad_block_rows(coo_to_block_ell(src, dst, val, (N, N), BLOCK,
                                            BLOCK), SHARDS)
    from_bell = make_backend(coo_to_block_ell(src, dst, val, (N, N), BLOCK,
                                              BLOCK), cfg, partition=part,
                             block_g=16, interpret=True)
    out["staged_equal"] = all(
        np.array_equal(np.asarray(b.cols), table.block_cols)
        and np.array_equal(np.asarray(b.vals).view(np.uint32),
                           table.values.view(np.uint32))
        for b in (bk, from_bell))

    # clean runs of other features, both granularities: no flag
    rng = np.random.default_rng(7)
    flags = []
    for gran in ("layer", "stripe"):
        for _ in range(2):
            h = stage_rows(part, padded, lambda lo, hi: rng.random(
                (hi - lo, F)).astype(np.float32))
            _, rep = gcn_apply(params, Graph(s=plan, h0=h), cfg,
                               backend=make(granularity=gran))
            flags.append(bool(rep.flag))
    out["clean_flags"] = flags

    # one upset in the last layer on each shard's stripe, at both
    # granularities
    per = bk.vals.shape[0] // SHARDS
    faults = []
    for shard in range(SHARDS):
        stripe = shard * per + 2
        for gran in ("layer", "stripe"):
            bad = make(inject=(1, stripe, 0, 4.0), granularity=gran)
            lg, checks = gcn_forward(params, graph, cfg, backend=bad)
            chk = checks[1]
            d = np.abs(np.asarray(chk.predicted) - np.asarray(chk.actual))
            changed = np.flatnonzero(
                (np.asarray(lg) != clean).any(axis=1)).tolist()
            faults.append({"shard": shard, "stripe": stripe,
                           "granularity": gran,
                           "flagged": np.flatnonzero(d > 1e-3).tolist(),
                           "changed_rows": changed})
    out["faults"] = faults
    out["rows_per_shard"] = per * BLOCK

    # the fused layer reads every H row on every shard, so it takes H
    # replicated; its hook too lands on the owning shard only
    fused = {}
    for inject in (None, (1, 2 * per + 1, 0, 4.0)):
        fk = make(fused_layer=True, inject=inject)
        lg, rep = gcn_apply(params, Graph(s=plan, h0=jnp.asarray(h0)), cfg,
                            backend=fk)
        fused[str(inject is not None)] = {
            "gap": float(np.abs(np.asarray(lg) - ref).max()
                         / np.abs(ref).max()),
            "flag": bool(rep.flag), "hits": fk.fused_hits}
    out["fused"] = fused
    return out


# -- tier-1: the child's results ------------------------------------------------

CHILD = textwrap.dedent("""
    import json
    import test_sharded_rows as t
    print(json.dumps(t.sharded_cases()))
""")


@pytest.fixture(scope="module")
def sharded():
    here = Path(__file__).resolve().parent
    env = {**os.environ, "PYTHONPATH": f"src:{here}",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={SHARDS}",
           "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, timeout=900, cwd=here.parent, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["devices"] == SHARDS
    return rec


def test_held_forward_matches_numpy(sharded):
    assert sharded["logit_gap"] < 1e-5, sharded["logit_gap"]
    assert sharded["padding_max"] == 0.0          # padded rows stay zero
    assert "graph" in sharded["logits_spec"]      # logits stay row-sharded
    assert sharded["clean_flag"] is False


def test_staging_is_the_one_host_table(sharded):
    assert sharded["staged_equal"] is True


def test_clean_runs_never_flag(sharded):
    assert sharded["clean_flags"] == [False] * 4


def test_fault_flags_on_every_shard(sharded):
    faults = sharded["faults"]
    assert {f["shard"] for f in faults} == set(range(SHARDS))
    for f in faults:
        if f["granularity"] == "stripe":
            assert f["flagged"] == [f["stripe"]], f   # the global stripe
        else:
            assert f["flagged"] == [0], f            # the one corner


def test_fused_layer_injects_on_the_owning_shard(sharded):
    clean, bad = sharded["fused"]["False"], sharded["fused"]["True"]
    assert clean["hits"] == bad["hits"] == 2          # both layers fused
    assert clean["gap"] < 1e-5 and clean["flag"] is False, clean
    assert bad["flag"] is True and bad["gap"] > 1e-3, bad


def test_fault_leaves_other_shards_bit_identical(sharded):
    per = sharded["rows_per_shard"]
    for f in sharded["faults"]:
        lo, hi = f["shard"] * per, (f["shard"] + 1) * per
        assert f["changed_rows"], f
        assert all(lo <= r < hi for r in f["changed_rows"]), f
        # only the faulted stripe's rows
        assert all(r // BLOCK == f["stripe"] for r in f["changed_rows"]), f


def test_staged_bytes_and_spans_carry_their_ids(sharded):
    per = sharded["padded_rows"] // BLOCK // SHARDS
    plan = plan_block_ell(*_graph()[:3], (N, N), BLOCK, BLOCK)
    tile_bytes = per * plan.width * (BLOCK * BLOCK * 4 + 4)
    assert sharded["staged_bytes"] == {str(d): tile_bytes
                                       for d in sharded["device_ids"]}
    stages = [ids for name, ids in sharded["spans"] if name == "gcn.stage"]
    feature_bytes = sharded["padded_rows"] // SHARDS * F * 4
    # the tile table's four shards, then the features' four
    assert sorted((s["shard"], s["bytes"]) for s in stages) == sorted(
        [(k, tile_bytes) for k in range(SHARDS)]
        + [(k, feature_bytes) for k in range(SHARDS)])
    exchanges = [ids for name, ids in sharded["spans"]
                 if name == "gcn.exchange"]
    rows = sharded["padded_rows"]
    assert [(s["layer"], s["bytes"]) for s in exchanges] == [
        (i, rows * (g + 1) * 4) for i, g in enumerate(DIMS[1:])]


# -- the layout, here -----------------------------------------------------------

def _loop_conversion(row, col, data, shape, bm, bk):
    """The per-tile loop the block-ELL conversion used to be: the reference
    the vectorized plan must match bit for bit."""
    m, k = shape
    row = np.asarray(row, np.int64)
    col = np.asarray(col, np.int64)
    data = np.asarray(data, np.float32)
    nbm, nbk = -(-m // bm), -(-k // bk)
    tile_id = (row // bm) * nbk + col // bk
    order = np.argsort(tile_id, kind="stable")
    uniq, starts = np.unique(tile_id[order], return_index=True)
    ends = np.append(starts[1:], tile_id.size)
    counts = np.zeros(nbm, np.int64)
    np.add.at(counts, uniq // nbk, 1)
    width = max(int(counts.max()) if counts.size else 1, 1)
    values = np.zeros((nbm, width, bm, bk), np.float32)
    block_cols = np.zeros((nbm, width), np.int32)
    slot = np.zeros(nbm, np.int64)
    for t, lo, hi in zip(uniq, starts, ends):
        i, j = int(t // nbk), int(t % nbk)
        idx = order[lo:hi]
        np.add.at(values[i, slot[i]], (row[idx] - i * bm, col[idx] - j * bk),
                  data[idx])
        block_cols[i, slot[i]] = j
        slot[i] += 1
    return block_cols, values


@pytest.mark.parametrize("seed", range(6))
def test_conversion_matches_the_loop_bit_for_bit(seed):
    rng = np.random.default_rng(seed)
    m, k = (int(x) for x in rng.integers(1, 400, 2))
    bm, bk = (int(x) for x in rng.choice([8, 16, 32, 128], 2))
    nnz = int(rng.integers(0, 2000))
    row, col = rng.integers(0, m, nnz), rng.integers(0, k, nnz)
    row = np.concatenate([row, row[:nnz // 3]])       # duplicates sum
    col = np.concatenate([col, col[:nnz // 3]])
    data = rng.normal(size=row.size).astype(np.float32)
    cols, vals = _loop_conversion(row, col, data, (m, k), bm, bk)
    bell = coo_to_block_ell(row, col, data, (m, k), bm, bk)
    assert np.array_equal(bell.block_cols, cols)
    assert np.array_equal(bell.values.view(np.uint32), vals.view(np.uint32))


@pytest.mark.parametrize("shards", [1, 2, 3, 4, 8])
def test_shard_tiles_equal_the_padded_table(shards):
    src, dst, val = _graph(shards)[:3]
    plan = plan_block_ell(src, dst, val, (N, N), BLOCK, BLOCK)
    bell = coo_to_block_ell(src, dst, val, (N, N), BLOCK, BLOCK)
    table = pad_block_rows(bell, shards)
    assert (plan.padded_cols, plan.width, plan.n_block_rows) == (
        bell.padded_cols, bell.width, bell.n_block_rows)
    per = table.n_block_rows // shards
    for k in range(shards):
        for cols, vals in (plan.tiles(k * per, (k + 1) * per),
                           bell.tiles(k * per, (k + 1) * per)):
            assert np.array_equal(cols, table.block_cols[k * per:
                                                         (k + 1) * per])
            assert np.array_equal(vals, table.values[k * per:(k + 1) * per])
