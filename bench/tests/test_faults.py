"""With the timed path broken underneath, a run must come out incorrect.

Each test plants one fault in the program under test (never in the
benchmark) and drives the rest of a run through the harness on the CPU.
The faults a cell can have here: an answer altered where it is produced;
half of a batch left out; a stale answer returned in place of a fresh one;
and a check that no longer flags.  The exchange between chips does not
exist in these one-chip cells."""
import jax.numpy as jnp
import pytest

FULL = ["pubmed.full", "cora.full", "pubmed.full_clustered"]
STREAM = ["pubmed.stream"]


def alter_kernel_output(monkeypatch):
    """One output element of the aggregation kernel changed after the
    kernel wrote it (its own checksum never sees the change)."""
    from repro.kernels.spmm_abft import ops
    real = ops.spmm_abft_kernel

    def broken(*args, **kw):
        out, sums, extra = real(*args, **kw)
        return out.at[0, 0].add(0.5), sums, extra
    monkeypatch.setattr(ops, "spmm_abft_kernel", broken)


@pytest.mark.parametrize("workload", FULL + STREAM)
def test_altered_answer_is_incorrect(monkeypatch, run_tiny, workload):
    alter_kernel_output(monkeypatch)
    out = run_tiny(workload)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("workload", FULL)
def test_half_the_rows_left_out_is_incorrect(monkeypatch, run_tiny,
                                             workload):
    import repro.engine as engine
    real = engine.gcn_apply

    def broken(*args, **kw):
        logits, report = real(*args, **kw)
        half = logits.shape[0] // 2
        return logits.at[half:].set(0.0), report
    monkeypatch.setattr(engine, "gcn_apply", broken)
    assert run_tiny(workload)["correct"] is False


@pytest.mark.parametrize("workload", STREAM)
def test_half_the_batch_left_out_is_incorrect(monkeypatch, run_tiny,
                                              workload):
    from repro.engine import streaming
    real = streaming.make_packed_serve_step

    def broken(*args, **kw):
        step = real(*args, **kw)

        def half(cols, vals, segments, h0):
            # the first half of the packed rows: the low slots, which every
            # batch fills
            logits, metrics = step(cols, vals, segments, h0)
            rows = logits.shape[0] // 2
            return logits.at[:rows].set(0.0), metrics
        return half
    monkeypatch.setattr(streaming, "make_packed_serve_step", broken)
    assert run_tiny(workload)["correct"] is False


@pytest.mark.parametrize("workload", FULL)
def test_stale_answer_is_incorrect(monkeypatch, run_tiny, workload):
    import repro.engine as engine
    real = engine.gcn_apply
    first = {}

    def broken(*args, **kw):
        logits, report = real(*args, **kw)
        first.setdefault("logits", logits)
        return first["logits"], report
    monkeypatch.setattr(engine, "gcn_apply", broken)
    assert run_tiny(workload)["correct"] is False


@pytest.mark.parametrize("workload", FULL + STREAM)
def test_blind_check_is_incorrect(monkeypatch, run_tiny, workload):
    """A check that never flags: the injected fault goes unseen.  Every
    verdict comes from ``abft._tripped``; the report's compiled program
    is traced afresh so that no earlier trace keeps the real one."""
    import jax

    from repro.core import abft

    monkeypatch.setattr(abft, "_tripped",
                        lambda d, scale, cfg: jnp.zeros(jnp.shape(d), bool))
    body = abft._summarize_compiled.__wrapped__

    def fresh(checks, cfg, tagging):     # a new function: no cached trace
        return body(checks, cfg, tagging)
    monkeypatch.setattr(abft, "_summarize_compiled", jax.jit(
        fresh, static_argnames=("cfg", "tagging")))
    out = run_tiny(workload)
    assert out["correct"] is False
    assert out["checks"]["fault_missed"]["value"] == 1
