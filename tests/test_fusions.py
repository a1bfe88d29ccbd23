"""Per-fusion residual auditor (``obs/fusions.py`` + ``report
fusions``, round 13) against the committed roofline profiles.

The auditor prices every profiled fusion against the HBM roofline and
allocates the step's compute residual across them the way
``obs/budget.py`` allocates the step wall: greedy clamp-to-remaining
with an explicit unattributed bucket, so the rows PROVABLY sum to the
residual instead of a top-N that quietly double-counts.  jax-free, like
everything under obs/.
"""

import json
import os

import pytest

from flexflow_tpu.obs import fusions

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROFILES = [
    os.path.join(ROOT, "examples", "profiles", p)
    for p in ("inception_v3_roofline.json", "alexnet_roofline.json")
]


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(params=PROFILES, ids=["inception", "alexnet"])
def profile(request):
    return _load(request.param)


# ---------------------------------------------------------------------------
# account invariants


def test_rows_sum_to_residual_exactly(profile):
    acc = fusions.fusion_account(profile)
    assert acc["schema"] == fusions.SCHEMA
    total = sum(r["excess_ms"] for r in acc["rows"])
    assert total + acc["unattributed_ms"] == pytest.approx(
        acc["residual_ms"], abs=1e-9)
    assert fusions.check_account(acc) == []


def test_rows_ranked_and_verdicted(profile):
    acc = fusions.fusion_account(profile, top_n=10)
    rows = acc["rows"]
    assert 0 < len(rows) <= 10
    raws = [r["excess_ms_raw"] for r in rows]
    assert raws == sorted(raws, reverse=True)
    for r in rows:
        assert r["verdict"] in ("fusable", "pallas_worthy",
                                "irreducible"), r
        assert r["floor_ms"] <= r["measured_ms"] + 1e-9, r
        assert r["excess_ms"] >= 0.0, r
        assert 0.0 <= r["share_of_residual"] <= 1.0, r
    assert 0.0 < acc["top3_frac"] <= 1.0


def test_mxu_rows_are_irreducible(profile):
    acc = fusions.fusion_account(profile)
    for r in acc["rows"]:
        if r["class"] == "mxu":
            assert r["verdict"] == "irreducible", r


def test_inception_names_the_shipped_consumer():
    profile = _load(PROFILES[0])
    acc = fusions.fusion_account(profile)
    by_kind = {r.get("rewrite"): r for r in acc["rows"]
               if r.get("predicted_win_ms") is not None}
    # the top residual consumer: the add_any gradient-accumulation
    # chain, rewritten by ops/fanout.py with a recorded roofline win
    assert by_kind["grad_fanout"]["predicted_win_ms"] > 0
    # the maxpool-backward select_and_scatter is priced like any other
    # row without shapes: at its measured time, no kernel, no win
    ss = [fusions._price_row(r, acc["mxu_eff"], 819e9)
          for r in profile["top_ops"]
          if r["name"].startswith("select_and_scatter")]
    assert ss
    for r in ss:
        assert r["floor_source"] == "unpriced"
        assert r["floor_ms"] == r["measured_ms"]
        assert "kernel" not in r and "predicted_win_ms" not in r


def test_residual_top_frac_in_unit_interval(profile):
    frac = fusions.residual_top_frac(profile)
    assert 0.0 < frac < 1.0


def test_render_is_textual_and_complete(profile):
    acc = fusions.fusion_account(profile)
    text = fusions.render_account(acc)
    for r in acc["rows"]:
        assert r["name"] in text
    assert "residual" in text


# ---------------------------------------------------------------------------
# tamper detection: check_account catches a broken sum


def test_check_account_flags_tampered_rows(profile):
    acc = fusions.fusion_account(profile)
    acc["rows"][0]["excess_ms"] += 0.5 * acc["residual_ms"]
    assert fusions.check_account(acc) != []


# ---------------------------------------------------------------------------
# the CLI: `report fusions` on the committed fixtures


def test_report_fusions_cli_json(capsys):
    from flexflow_tpu.apps import report

    lines = []
    rc = report.main(["fusions", *PROFILES, "--json"],
                     log=lines.append)
    assert rc == 0
    out = json.loads("\n".join(lines))
    assert out["violations"] == []
    assert len(out["accounts"]) == 2
    for acc in out["accounts"]:
        assert acc["schema"] == fusions.SCHEMA


def test_report_fusions_cli_errors_without_top_ops(tmp_path):
    from flexflow_tpu.apps import report

    bad = tmp_path / "no_ops.json"
    bad.write_text(json.dumps({"model": "x", "seconds_per_step": 0.1}))
    rc = report.main(["fusions", str(bad)], log=lambda *a: None)
    assert rc == 2
