"""Kernel contract auditor coverage (repro.analysis.kernel_audit/contracts).

Fixture geometries with deliberate violations — each yields exactly one
typed finding; a clean spec yields zero; the JSON report round-trips; the
full registry audits clean on the shipped tree; and the planners
(`gemm_block_plan`, `paged_kernel_plan`) provably never emit a geometry the
auditor rejects (property tests).
"""
import pathlib

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.analysis import contracts, kernel_audit, run
from repro.analysis.findings import Report

pytestmark = pytest.mark.analysis

REPO_ROOT = pathlib.Path(__file__).parent.parent

MiB = 1024 * 1024


def _geom(operands, grid=(2, 1), scalar_prefetch=(), scratch=0, suppress=None):
    return contracts.KernelGeometry(
        kernel="tests.fixture", grid=grid, operands=tuple(operands),
        scalar_prefetch=tuple(scalar_prefetch), scratch_bytes=scratch,
        tag="fixture", suppress=suppress or {})


def _findings(geom, budget=contracts.DEFAULT_VMEM_BUDGET):
    return contracts.check_geometry(geom, budget)


# ---------------------------------------------------------------------------
# the five deliberate violations — exactly one typed finding each
# ---------------------------------------------------------------------------

def test_f32_sublane_misaligned_block():
    # (7, 128) f32 block in a (14, 128) array: 7 is neither a multiple of the
    # f32 sublane tile (8) nor the full extent (14); divisibility is fine
    fs = _findings(_geom([contracts.OperandSpec(
        "x", (14, 128), "float32", (7, 128), lambda i, j: (i, j))]))
    assert [f.rule for f in fs] == ["tile-misaligned"], [f.format() for f in fs]


def test_int8_block_misaligned_to_32x128():
    # int8 wants (32, 128): a (16, 128) block in a (64, 128) array misses the
    # sublane tile without being the full extent
    fs = _findings(_geom([contracts.OperandSpec(
        "w", (64, 128), "int8", (16, 128), lambda i, j: (i, j))],
        grid=(4, 1)))
    assert [f.rule for f in fs] == ["tile-misaligned"], [f.format() for f in fs]


def test_vmem_over_budget_cell():
    # streamed (256, 256) f32 block double-buffers to 512 KiB > 256 KiB budget
    fs = _findings(_geom([contracts.OperandSpec(
        "x", (512, 256), "float32", (256, 256), lambda i, j: (i, 0))],
        grid=(2, 1)), budget=256 * 1024)
    assert [f.rule for f in fs] == ["vmem-overflow"], [f.format() for f in fs]


def test_f32_scalar_prefetch_operand():
    fs = _findings(_geom(
        [contracts.OperandSpec("x", (8, 128), "float32", (8, 128),
                               lambda i, j: (0, 0))],
        grid=(1, 1),
        scalar_prefetch=[contracts.ScalarSpec("lens", (4,), "float32")]))
    assert [f.rule for f in fs] == ["smem-illegal-dtype"], \
        [f.format() for f in fs]


def test_out_of_bounds_index_map():
    # 16/8 = 2 row blocks, but the map returns block (i + 1): cell i=1 -> 2
    fs = _findings(_geom([contracts.OperandSpec(
        "x", (16, 128), "float32", (8, 128), lambda i, j: (i + 1, 0))],
        grid=(2, 1)))
    assert [f.rule for f in fs] == ["index-oob"], [f.format() for f in fs]


# ---------------------------------------------------------------------------
# clean specs, remaining rules, suppressions
# ---------------------------------------------------------------------------

def test_clean_spec_zero_findings():
    fs = _findings(_geom([
        contracts.OperandSpec("a", (512, 256), "int8", (256, 256),
                              lambda i, j: (i, 0)),
        contracts.OperandSpec("o", (512, 128), "float32", (256, 128),
                              lambda i, j: (i, 0)),
    ], grid=(2, 1),
        scalar_prefetch=[contracts.ScalarSpec("lens", (4,), "int32")]))
    assert fs == []


def test_full_extent_edge_tile_is_legal():
    # a 100-row f32 block covering the whole axis: Mosaic pads one edge tile
    fs = _findings(_geom([contracts.OperandSpec(
        "x", (100, 128), "float32", (100, 128), lambda i, j: (0, 0))],
        grid=(1, 1)))
    assert fs == []


def test_unmasked_remainder_flagged_masked_passes():
    spec = dict(name="x", shape=(300, 128), dtype="float32",
                block=(128, 128), index_map=lambda i, j: (i, 0))
    fs = _findings(_geom([contracts.OperandSpec(**spec)], grid=(3, 1)))
    assert [f.rule for f in fs] == ["block-divisibility"]
    fs = _findings(_geom([contracts.OperandSpec(**spec, masked_axes=(0,))],
                         grid=(3, 1)))
    assert fs == []


def test_grid_empty():
    fs = _findings(_geom([contracts.OperandSpec(
        "x", (8, 128), "float32", (8, 128), lambda i, j: (0, 0))],
        grid=(0, 1)))
    assert [f.rule for f in fs] == ["grid-empty"]


def test_registry_suppression():
    fs = _findings(_geom([contracts.OperandSpec(
        "x", (14, 128), "float32", (7, 128), lambda i, j: (i, j))],
        suppress={"tile-misaligned": "fixture: known-odd geometry"}))
    assert len(fs) == 1 and fs[0].suppressed
    assert fs[0].suppress_reason == "fixture: known-odd geometry"


# ---------------------------------------------------------------------------
# report schema round-trip
# ---------------------------------------------------------------------------

def test_json_report_schema_roundtrip():
    fs = _findings(_geom([contracts.OperandSpec(
        "x", (14, 128), "float32", (7, 128), lambda i, j: (i, j))]))
    rep = Report(findings=fs, meta={"fixture": True})
    d = rep.to_dict()
    assert d["schema_version"] == 1
    assert d["counts"] == {"total": 1, "suppressed": 0, "new": 1}
    back = Report.from_json(rep.to_json())
    assert [f.fingerprint for f in back.findings] == \
        [f.fingerprint for f in rep.findings]
    assert back.findings[0] == rep.findings[0]
    assert back.meta == {"fixture": True}


def test_fingerprint_ignores_line_numbers():
    a = _findings(_geom([contracts.OperandSpec(
        "x", (14, 128), "float32", (7, 128), lambda i, j: (i, j))]))[0]
    import dataclasses
    b = dataclasses.replace(a, line=a.line + 40)
    assert a.fingerprint == b.fingerprint


# ---------------------------------------------------------------------------
# shipped tree: registry audits clean; wrappers never out-plan the auditor
# ---------------------------------------------------------------------------

def test_registry_audit_clean_on_shipped_tree():
    rep = kernel_audit.audit()
    assert rep.meta["cells"] >= 40          # all five kernels, real grids
    bad = [f.format() for f in rep.findings if not f.suppressed]
    assert not bad, "\n".join(bad)


def test_full_run_zero_unsuppressed():
    rep = run(REPO_ROOT)
    bad = [f.format() for f in rep.active()]
    assert not bad, "\n".join(bad)


def test_resident_pool_blocking_rejected():
    """Regression for the paged_attention fix: blocking a whole production
    pool into VMEM (the pre-fix BlockSpec) must be auditor-rejected; the
    shipped ANY-space + chunk-scratch contract is clean at the same size."""
    n_pool, bs, kh, d = 2049, 16, 16, 128
    resident = _geom([contracts.OperandSpec(
        "k_pool", (n_pool, bs, kh, d), "float32", (n_pool, bs, kh, d),
        lambda bi, qi, si: (0, 0, 0, 0))], grid=(4, 1, 1))
    assert any(f.rule == "vmem-overflow" for f in _findings(resident))
    from repro.launch.autotune import paged_kernel_plan
    max_len = n_pool * bs // 4
    kv_chunk, n_splits = paged_kernel_plan(max_len, bs, batch=4, kv_heads=kh,
                                           head_dim=d)
    fs = kernel_audit.check_paged_geometry(
        kv_chunk, n_splits, max_len=max_len, block_size=bs, batch=4,
        kv_heads=kh, head_dim=d)
    assert fs == []


def test_engine_default_geometry_clean():
    # ServeEngine defaults: max_slots=4, max_len=64, block_size=8
    fs = kernel_audit.check_paged_geometry(
        64, 1, max_len=64, block_size=8, batch=4, kv_heads=4, head_dim=64)
    assert fs == []


def test_flash_envelope_boundary():
    env = kernel_audit.flash_kv_envelope(128)
    assert env >= 2048
    from repro.kernels import flash_attention
    over = flash_attention.tpu_contract(1, 1, 128, env * 4, 128)
    assert any(f.rule == "vmem-overflow"
               for f in contracts.check_geometry(over))


def test_block_picker_matches_ops():
    """kernel_audit mirrors ops._blocks' TPU arithmetic — pin them together."""
    from repro.kernels import ops
    for dim in (1, 7, 64, 100, 128, 200, 256, 300, 512, 1000, 4096):
        for pref in (128, 256, 512):
            assert kernel_audit._blocks(dim, pref) == \
                ops._blocks(dim, pref, 128), (dim, pref)


# ---------------------------------------------------------------------------
# planner properties: no plan the auditor rejects
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(max_len=st.integers(min_value=8, max_value=65536),
       block_size=st.sampled_from([8, 16, 32]),
       batch=st.integers(min_value=1, max_value=16),
       kv_heads=st.sampled_from([1, 2, 4, 8, 16]),
       q_per_kv=st.sampled_from([1, 2, 4, 8]),
       head_dim=st.sampled_from([64, 128, 256]),
       kv_dtype=st.sampled_from(["float32", "int8"]),
       allow_splits=st.booleans(),
       budget_mib=st.sampled_from([2, 4, 16]))
def test_paged_plan_never_rejected(max_len, block_size, batch, kv_heads,
                                   q_per_kv, head_dim, kv_dtype,
                                   allow_splits, budget_mib):
    from repro.launch.autotune import paged_kernel_plan
    budget = budget_mib * MiB
    try:
        kv_chunk, n_splits = paged_kernel_plan(
            max_len, block_size, batch=batch, kv_heads=kv_heads,
            allow_splits=allow_splits, head_dim=head_dim, q_per_kv=q_per_kv,
            kv_dtype=kv_dtype, vmem_budget=budget)
    except kernel_audit.ContractViolation:
        return      # refusing to plan an unlowerable geometry is also correct
    fs = kernel_audit.check_paged_geometry(
        kv_chunk, n_splits, max_len=max_len, block_size=block_size,
        batch=batch, kv_heads=kv_heads, head_dim=head_dim,
        q_per_kv=q_per_kv, kv_dtype=kv_dtype, vmem_budget=budget)
    assert fs == [], "\n".join(f.format() for f in fs)
    assert kv_chunk % block_size == 0 and n_splits >= 1


@settings(max_examples=60, deadline=None)
@given(m=st.integers(min_value=1, max_value=4096),
       n=st.integers(min_value=1, max_value=4096),
       k=st.integers(min_value=1, max_value=4096),
       kernel=st.sampled_from(["delta", "systolic", "lut"]),
       rank=st.sampled_from([0, 1, 10, 21]),
       budget_mib=st.sampled_from([1, 4, 16]))
def test_gemm_plan_never_rejected(m, n, k, kernel, rank, budget_mib):
    budget = budget_mib * MiB
    try:
        bm, bn, bk = kernel_audit.gemm_block_plan(
            m, n, k, kernel=kernel, rank=rank, vmem_budget=budget)
    except kernel_audit.ContractViolation:
        return
    mod = kernel_audit._gemm_module(kernel)
    geom = kernel_audit._gemm_contract(mod, m, n, k, bm, bn, bk, rank, 256)
    fs = [f for f in contracts.check_geometry(geom, budget)
          if not f.suppressed]
    assert fs == [], "\n".join(f.format() for f in fs)


def test_gemm_plan_shrinks_under_tight_budget():
    full = kernel_audit.gemm_block_plan(4096, 4096, 4096, kernel="delta",
                                        rank=21)
    # the rank-21 correction factors stream as (21, bm, bk) / (21, bk, bn)
    # float32 blocks beside A and B, so they dominate the cell's VMEM
    tight = kernel_audit.gemm_block_plan(4096, 4096, 4096, kernel="delta",
                                         rank=21, vmem_budget=8 * MiB)
    assert full == (256, 256, 128)
    # a tighter budget shrinks the plan (some block halved) but never below
    # the MXU tile edge — and the result is still contract-clean
    import math
    assert math.prod(tight) < math.prod(full) and min(tight) >= 128
