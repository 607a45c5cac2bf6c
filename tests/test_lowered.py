"""Lowered-program analysis tier (DESIGN.md §13): the L001–L004 checks
behind ``python -m repro.analysis --lowered``.

Three claims are pinned, mirroring the contract-layer tests:

* **the surface is clean** — one full CLI run (the real entry point,
  with its forced multi-device host platform) over every kernel ×
  backend × shape, method × mesh, serving family and layout case
  returns zero findings against the committed fingerprints;
* **enumeration is total** — the stats the driver prints equal the
  registry sizes computed independently here, so "0 findings" can
  never mean "0 surfaces lowered";
* **every check actually catches its regression** — four deliberate
  regressions injected via ``REPRO_LOWERED_INJECT`` (an extra
  all-gather, a skewed uplink payload model, a misaligned Pallas
  block, a dropped donation) each produce exactly the matching L-rule
  finding through the same public CLI path.

Plus jax-free unit coverage of the shared cost helpers
(``analysis/lowered/costs.py``), the fingerprint store and the layout
lint rules on synthetic layouts.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.analysis.lowered import costs, fingerprints
from repro.analysis.lowered.layout_lint import lint_layout
from repro.kernels.common import BlockLayout, OperandLayout

pytestmark = pytest.mark.analysis

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run_cli(*extra, inject=None, timeout=1500):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("REPRO_LOWERED_INJECT", None)
    env.pop("XLA_FLAGS", None)     # the CLI branch must set this itself
    if inject:
        env["REPRO_LOWERED_INJECT"] = inject
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--lowered",
         "--no-baseline", "--format", "json", *extra],
        capture_output=True, text=True, env=env, cwd=REPO,
        timeout=timeout)
    assert proc.stdout, proc.stderr
    return proc.returncode, json.loads(proc.stdout)


# ---------------------------------------------------------------------------
# the whole lowered surface is clean, and enumeration is total
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def full_run():
    """One full CLI run shared by the clean-surface and enumeration
    tests (the expensive part: every round program compiles twice)."""
    return _run_cli()


def test_whole_lowered_surface_is_clean(full_run):
    code, out = full_run
    assert out["findings"] == [], json.dumps(out["findings"], indent=1)
    assert code == 0


def test_kernel_enumeration_is_total(full_run):
    from repro.analysis.contracts import shapes
    from repro.kernels import dispatch

    _, out = full_run
    decls = dispatch.kernel_contracts()
    expect = sum(
        (len(backends) + 1) * len(list(shapes.kernel_cases(
            decls[k].family)))
        for k, backends in dispatch.available_kernels().items())
    assert out["stats"]["kernel_lowered"] == expect
    assert expect >= 22


def test_layout_enumeration_is_total(full_run):
    from repro.analysis.contracts import shapes
    from repro.kernels import dispatch

    _, out = full_run
    decls = dispatch.kernel_contracts()
    expect = sum(len(list(shapes.kernel_cases(decls[k].family)))
                 for k in dispatch.kernel_layouts())
    assert out["stats"]["layout_cases"] == expect
    assert expect >= 6


def test_program_enumeration_is_total(full_run):
    from repro.analysis.contracts.serving import ARCH_FAMILIES
    from repro.analysis.lowered.surfaces import MESHES
    from repro.federated.methods.registry import available_methods

    _, out = full_run
    assert out["stats"]["round_programs"] == (
        len(available_methods()) * len(MESHES))
    assert out["stats"]["serving_programs"] == len(ARCH_FAMILIES)


def test_fingerprints_cover_every_compiled_surface(full_run):
    """The committed fingerprint file and the enumerated surfaces are
    the same set — no budget escapes the diff, nothing is stale."""
    from repro.analysis.contracts.serving import ARCH_FAMILIES
    from repro.analysis.lowered.surfaces import MESHES
    from repro.federated.methods.registry import available_methods

    committed = fingerprints.load("cpu")
    assert committed is not None
    expect = {f"round:{m}:{tag}" for m in available_methods()
              for tag, _ in MESHES}
    expect |= {f"serving:{a}" for a in ARCH_FAMILIES}
    assert set(committed) == expect


# ---------------------------------------------------------------------------
# each check catches its injected regression (public CLI path)
# ---------------------------------------------------------------------------


def _rules(out):
    return [f["rule"] for f in out["findings"]]


def test_injected_collective_is_caught():
    """A re-replicating sharding constraint inside the round program
    adds all-gathers the committed fingerprint does not budget for."""
    code, out = _run_cli("--surface", "round:fedit:4x2",
                         inject="collective")
    assert code == 1
    assert _rules(out) == ["L001"]
    assert "all-gather" in out["findings"][0]["message"]


def test_injected_cost_skew_is_caught():
    """A 3x-skewed analytical uplink payload model diverges from the
    payload traced out of the actual round program."""
    code, out = _run_cli("--surface", "round:fedit:4x2", inject="cost")
    assert code == 1
    assert _rules(out) == ["L002"]
    assert "uplink" in out["findings"][0]["message"]


def test_injected_bad_layout_is_caught():
    """A (7, 100) block on a (32, 32) fp32 operand violates sublane
    granularity, lane alignment and coverage at once."""
    code, out = _run_cli("--surface", "layout:", inject="layout")
    assert code == 1
    assert set(_rules(out)) == {"L003"}
    msgs = " ".join(f["message"] for f in out["findings"])
    assert "sublane" in msgs and "lane" in msgs and "covered" in msgs
    assert all(f["line_text"] == "layout:flash_attention:injected"
               for f in out["findings"])


def test_injected_dropped_donation_is_caught():
    """Compiling the round program without its donate_argnums loses
    every adapter-buffer alias; L004 reports the exact indices."""
    code, out = _run_cli("--surface", "round:fedit:4x2",
                         inject="donation")
    assert code == 1
    assert _rules(out) == ["L004"]
    assert "alias" in out["findings"][0]["message"]


# ---------------------------------------------------------------------------
# cost helpers (jax-free)
# ---------------------------------------------------------------------------

_HLO = """\
HloModule jit_fn, input_output_alias={ {0}: (12, {}, may-alias), \
{1}: (3, {}, may-alias) }, entry_computation_layout=...

ENTRY main {
  ag = f32[8,128]{1,0} all-gather(x), replica_groups={{0,1}}
  ar = f32[8,128]{1,0} all-reduce(ag), to_apply=add
  ars = f32[8,128]{1,0} all-reduce-start(ar), to_apply=add
  cp = f32[8,128]{1,0} collective-permute(ars)
  of = token[] outfeed(data, tok)
}
"""


def test_collective_counts_and_transfers():
    counts = costs.collective_counts(_HLO)
    assert counts["all-gather"] == 1
    # the async all-reduce-start form counts once, as an all-reduce
    assert counts["all-reduce"] == 2
    assert counts["collective-permute"] == 1
    assert counts["reduce-scatter"] == 0
    assert costs.transfer_count(_HLO) == 1


def test_alias_sources_parses_the_alias_table():
    assert costs.alias_sources(_HLO) == {12, 3}
    assert costs.alias_sources("HloModule jit_fn, entry=...") == set()


def test_collective_bytes_schema():
    got = costs.collective_bytes(_HLO)
    assert got["count"] == 4
    assert got["all-gather"] == 8 * 128 * 4


def test_stablehlo_counts():
    text = ('%0 = "stablehlo.all_gather"(%arg0)\n'
            '%1 = "stablehlo.all_reduce"(%0)\n'
            '%2 = "stablehlo.all_reduce"(%1)\n')
    counts = costs.stablehlo_collective_counts(text)
    assert counts["all-gather"] == 1
    assert counts["all-reduce"] == 2
    assert costs.stablehlo_transfer_count(text) == 0


def test_cost_dict_normalizes_list_form():
    class Fake:
        def cost_analysis(self):
            return [{"flops": 7.0}]     # jax<0.5 list form

    assert costs.cost_dict(Fake()) == {"flops": 7.0}
    assert costs.device_costs(Fake()) == {"flops": 7.0, "bytes": 0.0}


def test_roofline_terms_bottleneck():
    t = costs.roofline_terms(costs.PEAK_FLOPS, 0.0, 0.0)
    assert t["bottleneck"] == "compute" and t["t_compute"] == 1.0
    t = costs.roofline_terms(0.0, costs.HBM_BW, 0.0)
    assert t["bottleneck"] == "memory" and t["t_memory"] == 1.0
    t = costs.roofline_terms(0.0, 0.0, costs.ICI_BW)
    assert t["bottleneck"] == "collective" and t["t_collective"] == 1.0


# ---------------------------------------------------------------------------
# fingerprint store
# ---------------------------------------------------------------------------


def test_fingerprint_roundtrip_and_diff(tmp_path):
    path = tmp_path / "fp.json"
    fp = fingerprints.fingerprint({"all-reduce": 3}, 1)
    fingerprints.save("cpu", {"round:x:4x2": fp}, path)
    fingerprints.save("tpu", {"round:x:4x2": fp}, path)   # preserves cpu
    assert fingerprints.load("cpu", path) == {"round:x:4x2": fp}
    assert fingerprints.load("gpu", path) is None
    assert fingerprints.diff(fp, fp) == []
    drift = fingerprints.diff(fp, {"all-reduce": 5, "transfers": 1})
    assert drift == ["all-reduce: expected 3, got 5 (+2)"]


# ---------------------------------------------------------------------------
# layout lint rules (synthetic layouts)
# ---------------------------------------------------------------------------


def _layout(block, shape=(64, 128), *, dtype="float32", accum="float32",
            memory="vmem", scratch=()):
    op = OperandLayout(shape, block, dtype, memory=memory)
    return BlockLayout(kernel="k", grid=(1,), operands={"x": op},
                       outputs={}, scratch=scratch, accum_dtype=accum)


def test_lint_clean_layout():
    assert lint_layout(_layout((8, 128))) == []


def test_lint_sublane_has_no_full_dim_exemption():
    # a (1, 1) VMEM block still burns a whole (8, 128) tile — the exact
    # shape of the old SSD per-head scalar bug
    msgs = lint_layout(_layout((1, 1), (64, 1)))
    assert any("sublane" in m for m in msgs)


def test_lint_lane_full_dim_exemption():
    # lane == full array dim is legitimate (narrow operands)
    assert lint_layout(_layout((8, 32), (64, 32))) == []
    msgs = lint_layout(_layout((8, 32), (64, 128)))
    assert any("lane" in m for m in msgs)


def test_lint_smem_scalars_are_tile_exempt():
    # whole-array SMEM scalars need no tile alignment ...
    assert lint_layout(_layout((8,), (8,), memory="smem")) == []
    assert lint_layout(_layout((8, 1), (8, 1), memory="smem")) == []
    # ... but a partial SMEM block is what the TPU compiler refuses
    msgs = lint_layout(_layout((1, 1), (8, 1), memory="smem"))
    assert any("SMEM" in m for m in msgs)


def test_lint_coverage():
    msgs = lint_layout(_layout((8, 128), (60, 128)))
    assert any("not covered" in m for m in msgs)


def test_lint_accumulator_dtype():
    msgs = lint_layout(_layout((8, 128), accum="bfloat16"))
    assert any("accumulator" in m for m in msgs)


def test_lint_vmem_budget():
    big = OperandLayout((65536, 65536), (8192, 8192), "float32")
    msgs = lint_layout(BlockLayout(kernel="k", grid=(1,),
                                   operands={"x": big}, outputs={}))
    assert any("VMEM" in m for m in msgs)
