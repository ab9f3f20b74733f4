"""tpulint's own test suite: every rule has a firing positive fixture and a
silent negative fixture, suppressions need justifications, the JSON reporter
keeps its schema, and the production tree itself stays lint-clean."""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.tpulint.core import (  # noqa: E402
    RULE_NO_JUSTIFICATION,
    RULE_PARSE_ERROR,
    RULE_STALE_SUPPRESSION,
    RULE_UNKNOWN_RULE,
    analyze_file,
    analyze_source,
    iter_py_files,
    run_paths,
)
from tools.tpulint.reporters import (  # noqa: E402
    render_json,
    render_rule_list,
    render_sarif,
    render_text,
)
from tools.tpulint.rules import RULES  # noqa: E402

FIXTURES = REPO / "tests" / "lint_fixtures"
WPA_FIXTURES = FIXTURES / "wpa"
SHP_FIXTURES = FIXTURES / "shp"
SPD_FIXTURES = FIXTURES / "spd"
RULE_IDS = ["TPU001", "TPU002", "TPU003", "TPU004", "TPU005", "TPU006",
            "TPU007", "ASY001", "ASY002", "OBS001", "OBS002", "OBS003"]
WPA_RULE_IDS = ["WPA001", "WPA002", "WPA003", "WPA004"]
SHP_RULE_IDS = ["SHP001", "SHP002", "SHP003", "SHP004"]
SPD_RULE_IDS = ["SPD001", "SPD002", "SPD003", "SPD004", "SPD005"]
ALL_RULE_IDS = RULE_IDS + WPA_RULE_IDS + SHP_RULE_IDS + SPD_RULE_IDS


# ------------------------------------------------------------------ registry

def test_registry_has_the_documented_rule_set():
    assert sorted(RULES) == sorted(ALL_RULE_IDS)


def test_list_rules_mentions_every_id():
    listing = render_rule_list()
    for rule_id in ALL_RULE_IDS:
        assert rule_id in listing


# ------------------------------------------------------------ fixture corpus

@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_positive_fixture_fires(rule_id):
    findings = analyze_file(FIXTURES / f"{rule_id.lower()}_pos.py")
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, f"{rule_id} did not fire on its positive fixture"
    assert all(not f.suppressed for f in hits)


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_negative_fixture_is_silent(rule_id):
    findings = analyze_file(FIXTURES / f"{rule_id.lower()}_neg.py")
    assert [f for f in findings if f.rule == rule_id] == []


def test_negative_fixtures_are_fully_clean():
    # negatives must not trip OTHER rules either, or the corpus is confusing
    for neg in sorted(FIXTURES.glob("*_neg.py")):
        findings = analyze_file(neg)
        assert findings == [], f"{neg.name}: {[f.rule for f in findings]}"


def test_obs002_suppressed_fixture_is_silenced_with_justification():
    # the pushgateway pattern (ephemeral per-push registry) is the one
    # sanctioned in-function construction; it rides on a justified disable
    findings = analyze_file(FIXTURES / "obs002_sup.py")
    hits = [f for f in findings if f.rule == "OBS002"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)


def test_obs003_suppressed_fixture_is_silenced_with_justification():
    # a genuinely bounded "id-shaped" label set (fixed tenant roster) is the
    # sanctioned exception; it rides on a justified disable
    findings = analyze_file(FIXTURES / "obs003_sup.py")
    hits = [f for f in findings if f.rule == "OBS003"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)


def test_asy001_fires_on_blocking_sleep_in_async_retry_helper():
    # the resilience-layer hazard: jittered-backoff helpers must use
    # asyncio.sleep — a time.sleep between retries parks every coroutine
    findings = analyze_file(FIXTURES / "asy001_pos.py")
    hits = [f for f in findings if f.rule == "ASY001" and f.line > 13]
    assert hits, "ASY001 missed the blocking backoff inside retry_with_backoff"
    assert all(not f.suppressed for f in hits)


# -------------------------------------------- whole-program fixture corpus
#
# Each WPA fixture is a multi-file mini-project: the hazard is only visible
# when the analyzer resolves imports / class attributes / thread spawns
# across module boundaries, so these run through run_paths (which includes
# the program pass), not analyze_file.

@pytest.mark.parametrize("rule_id", WPA_RULE_IDS)
def test_wpa_positive_fixture_fires(rule_id):
    findings, _ = run_paths([WPA_FIXTURES / f"{rule_id.lower()}_pos"])
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, f"{rule_id} did not fire on its positive fixture package"
    assert all(not f.suppressed for f in hits)


@pytest.mark.parametrize("rule_id", WPA_RULE_IDS)
def test_wpa_negative_fixture_is_silent(rule_id):
    findings, _ = run_paths([WPA_FIXTURES / f"{rule_id.lower()}_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


@pytest.mark.parametrize("rule_id", WPA_RULE_IDS)
def test_wpa_suppressed_fixture_is_silenced_with_justification(rule_id):
    findings, _ = run_paths([WPA_FIXTURES / f"{rule_id.lower()}_sup"])
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    # a used suppression must not be swept as stale
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


# The SHP (shapeflow) fixtures follow the WPA convention: each rule has a
# pos/neg/sup mini-package, and the SHP001 positive is deliberately
# cross-module — the source is in serving.py, the sink in shapes.py, so
# only the interprocedural taint pass can connect them.

@pytest.mark.parametrize("rule_id", SHP_RULE_IDS)
def test_shp_positive_fixture_fires(rule_id):
    findings, _ = run_paths([SHP_FIXTURES / f"{rule_id.lower()}_pos"])
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, f"{rule_id} did not fire on its positive fixture package"
    assert all(not f.suppressed for f in hits)
    assert [f.rule for f in findings] == [rule_id] * len(hits)


@pytest.mark.parametrize("rule_id", SHP_RULE_IDS)
def test_shp_negative_fixture_is_silent(rule_id):
    findings, _ = run_paths([SHP_FIXTURES / f"{rule_id.lower()}_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


@pytest.mark.parametrize("rule_id", SHP_RULE_IDS)
def test_shp_suppressed_fixture_is_silenced_with_justification(rule_id):
    findings, _ = run_paths([SHP_FIXTURES / f"{rule_id.lower()}_sup"])
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


def test_shp001_message_carries_cross_module_taint_chain():
    """Every SHP001 must ship its witness: the source step, each hop, and
    the sink, with file:line anchors — here spanning two modules."""
    findings, _ = run_paths([SHP_FIXTURES / "shp001_pos"])
    (hit,) = [f for f in findings if f.rule == "SHP001"]
    assert hit.taint_chain and len(hit.taint_chain) >= 3
    assert "Taint:" in hit.message
    assert "len(requests)" in hit.taint_chain[0]
    assert "serving.py" in hit.taint_chain[0]  # source module
    assert "shapes.py" in hit.taint_chain[-1]  # sink module
    for step in hit.taint_chain:
        assert ":" in step and "[" in step  # every step carries file:line


# The live-index compactor extends the SHP001 alphabet: the repack gather
# vector must be sized by the CAPACITY bucket, not by the live-row count
# that survives a tombstone sweep (retrieval/device_index.py sizes the
# source vector at t.capacity for exactly this reason — one repack program
# per capacity rung, any survivor count).

def test_shp001_compact_positive_catches_survivor_sized_repack():
    findings, _ = run_paths([SHP_FIXTURES / "shp001_compact_pos"])
    hits = [f for f in findings if f.rule == "SHP001" and not f.suppressed]
    assert hits, "survivor-count-sized repack vector escaped the taint pass"
    (hit,) = hits
    assert "len(docs)" in hit.taint_chain[0]
    assert "compactor.py" in hit.taint_chain[0]  # source module
    assert "repack.py" in hit.taint_chain[-1]  # sink module


def test_shp001_compact_negative_is_silent():
    findings, _ = run_paths([SHP_FIXTURES / "shp001_compact_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_shp001_compact_suppressed_is_silenced_with_justification():
    findings, _ = run_paths([SHP_FIXTURES / "shp001_compact_sup"])
    hits = [f for f in findings if f.rule == "SHP001"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


# Segment-packed ring prefill extends both SHP alphabets: the [1, width]
# ring buffer must be sized by the SP_RING_BUCKETS ladder, not by the raw
# token count of whichever long prompts packed into the wave
# (serving/engine.py routes every packed pass through _ring_width /
# sp_ring_bucket_ladder for exactly this reason — one compiled ring
# program per ladder entry, any wave composition), and a class dispatching
# ring passes at ladder widths must precompile them in warmup.

def test_shp001_ring_positive_catches_wave_sized_buffer():
    findings, _ = run_paths([SHP_FIXTURES / "shp001_ring_pos"])
    hits = [f for f in findings if f.rule == "SHP001" and not f.suppressed]
    assert hits, "wave-token-sized ring buffer escaped the taint pass"
    (hit,) = hits
    assert "len(tokens)" in hit.taint_chain[0]
    assert "scheduler.py" in hit.taint_chain[0]  # source module
    assert "pack.py" in hit.taint_chain[-1]  # sink module


def test_shp001_ring_negative_is_silent():
    findings, _ = run_paths([SHP_FIXTURES / "shp001_ring_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_shp001_ring_suppressed_is_silenced_with_justification():
    findings, _ = run_paths([SHP_FIXTURES / "shp001_ring_sup"])
    hits = [f for f in findings if f.rule == "SHP001"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


def test_shp002_ring_positive_flags_unwarmed_ring_ladder():
    findings, _ = run_paths([SHP_FIXTURES / "shp002_ring_pos"])
    hits = [f for f in findings if f.rule == "SHP002" and not f.suppressed]
    assert any("RingPrefillServer" in f.message for f in hits), (
        "ring class with no warmup escaped SHP002")


def test_shp002_ring_negative_is_silent():
    findings, _ = run_paths([SHP_FIXTURES / "shp002_ring_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_shp002_ring_suppressed_is_silenced_with_justification():
    findings, _ = run_paths([SHP_FIXTURES / "shp002_ring_sup"])
    hits = [f for f in findings if f.rule == "SHP002"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


# The fused decode step extends both SHP alphabets once more: the
# spec-verify window of the fused kernel grid must be the STATIC k+1 the
# engine compiled (short drafts pad — ops/fused_decode.py scores a fixed
# [rows, S] window per bucket), never the live draft length, and a class
# dispatching the fused burst at row buckets must precompile the whole
# (bucket, has_prefill, filter) variant set in warmup — exactly what
# serving/engine.py's fused warmup ladder exists for.

def test_shp001_fused_positive_catches_draft_sized_window():
    findings, _ = run_paths([SHP_FIXTURES / "shp001_fused_pos"])
    hits = [f for f in findings if f.rule == "SHP001" and not f.suppressed]
    assert hits, "draft-length-sized fused window escaped the taint pass"
    (hit,) = hits
    assert "len(draft_tokens)" in hit.taint_chain[0]
    assert "burst.py" in hit.taint_chain[0]  # source module
    assert "grid.py" in hit.taint_chain[-1]  # sink module


def test_shp001_fused_negative_is_silent():
    findings, _ = run_paths([SHP_FIXTURES / "shp001_fused_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_shp001_fused_suppressed_is_silenced_with_justification():
    findings, _ = run_paths([SHP_FIXTURES / "shp001_fused_sup"])
    hits = [f for f in findings if f.rule == "SHP001"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


def test_shp002_fused_positive_flags_unwarmed_fused_ladder():
    findings, _ = run_paths([SHP_FIXTURES / "shp002_fused_pos"])
    hits = [f for f in findings if f.rule == "SHP002" and not f.suppressed]
    assert any("FusedStepEngine" in f.message for f in hits), (
        "fused-step class with no warmup escaped SHP002")


def test_shp002_fused_negative_is_silent():
    findings, _ = run_paths([SHP_FIXTURES / "shp002_fused_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_shp002_fused_suppressed_is_silenced_with_justification():
    findings, _ = run_paths([SHP_FIXTURES / "shp002_fused_sup"])
    hits = [f for f in findings if f.rule == "SHP002"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


# The SPD (spmdflow) fixtures follow the same convention: each rule has a
# pos/neg/sup mini-package.  The SPD001 positive splits the mesh
# construction and the bad collective across modules; the SPD002 positive
# routes one donation through a helper so the witness must chain the hop.

@pytest.mark.parametrize("rule_id", SPD_RULE_IDS)
def test_spd_positive_fixture_fires(rule_id):
    findings, _ = run_paths([SPD_FIXTURES / f"{rule_id.lower()}_pos"])
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, f"{rule_id} did not fire on its positive fixture package"
    assert all(not f.suppressed for f in hits)
    assert [f.rule for f in findings] == [rule_id] * len(hits)


@pytest.mark.parametrize("rule_id", SPD_RULE_IDS)
def test_spd_negative_fixture_is_silent(rule_id):
    findings, _ = run_paths([SPD_FIXTURES / f"{rule_id.lower()}_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


@pytest.mark.parametrize("rule_id", SPD_RULE_IDS)
def test_spd_suppressed_fixture_is_silenced_with_justification(rule_id):
    findings, _ = run_paths([SPD_FIXTURES / f"{rule_id.lower()}_sup"])
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


def test_spd001_witness_names_the_unbound_axis_and_known_axes():
    findings, _ = run_paths([SPD_FIXTURES / "spd001_pos"])
    (hit,) = [f for f in findings if f.rule == "SPD001"]
    assert "'pp'" in hit.message and "dp" in hit.message and "tp" in hit.message
    assert hit.taint_chain
    assert "psum" in hit.taint_chain[-1]
    assert "collect.py" in hit.taint_chain[-1]


def test_spd002_witness_chains_the_helper_hop():
    """The drive->_flush->jit donation must carry every hop: the helper
    that consumed the parameter, the jitted callee that donated it, and
    the stale read, each with a file:line anchor."""
    findings, _ = run_paths([SPD_FIXTURES / "spd002_pos"])
    hits = [f for f in findings if f.rule == "SPD002"]
    assert len(hits) == 2
    chained = [f for f in hits if any("_flush" in s for s in (f.taint_chain or []))]
    (via_helper,) = chained
    assert len(via_helper.taint_chain) >= 3
    assert "update_pool" in " ".join(via_helper.taint_chain)
    assert "read again" in via_helper.taint_chain[-1]
    for step in via_helper.taint_chain:
        assert ":" in step and "[" in step  # every step carries file:line


def test_spd_rules_have_stale_suppression_sweep_and_unknown_exit(tmp_path):
    """LNT002 covers SPD directives: a justified disable that matches no
    SPD finding is swept; a misspelled SPD id is LNT001."""
    (tmp_path / "mod.py").write_text(
        "def fine(pool):\n"
        "    # tpulint: disable=SPD002 -- historical; the donation moved behind a rebind\n"
        "    return pool\n"
    )
    findings, _ = run_paths([tmp_path])
    assert [f.rule for f in findings] == [RULE_STALE_SUPPRESSION]
    (tmp_path / "mod.py").write_text(
        "def fine(pool):\n"
        "    # tpulint: disable=SPD999 -- no such rule\n"
        "    return pool\n"
    )
    findings, _ = run_paths([tmp_path])
    assert RULE_UNKNOWN_RULE in {f.rule for f in findings}


def test_cli_unknown_spd_suppression_exits_3(tmp_path):
    target = tmp_path / "mod.py"
    target.write_text(
        "def fine(pool):\n"
        "    # tpulint: disable=SPD999 -- misspelled id\n"
        "    return pool\n"
    )
    assert _run_cli(str(target)).returncode == 3


def test_spd_baseline_roundtrip(tmp_path):
    """--write-baseline fingerprints SPD findings like every other rule,
    and the baselined run exits clean."""
    baseline = tmp_path / "baseline.json"
    target = "tests/lint_fixtures/spd/spd001_pos"
    assert _run_cli(target).returncode == 1
    assert _run_cli(target, "--write-baseline", str(baseline)).returncode == 0
    payload = json.loads(baseline.read_text())
    assert any(fp.startswith("SPD001::") for fp in payload["fingerprints"])
    proc = _run_cli(target, "--baseline", str(baseline), "--format", "json")
    assert proc.returncode == 0
    out = json.loads(proc.stdout)
    assert out["stats"]["baselined"] > 0


# ------------------------------------------------------- planted regressions
# Mutation tests against the REAL tree: re-introduce the two classes of bug
# the shapeflow pass exists to catch, and prove it catches them.

def _mutated_tree(tmp_path, relpath: str, needle: str, replacement: str) -> Path:
    src_root = REPO / "githubrepostorag_tpu"
    dst = tmp_path / "githubrepostorag_tpu"
    shutil.copytree(src_root, dst, ignore=shutil.ignore_patterns("__pycache__"))
    target = dst / relpath
    text = target.read_text()
    assert needle in text, f"mutation needle vanished from {relpath}"
    target.write_text(text.replace(needle, replacement, 1))
    return dst


def test_planted_engine_debucketing_is_caught_as_shp001(tmp_path):
    """Strip the bucket barrier from the packed wave's row sizing: the
    request-derived segment count then reaches the dispatch shapes raw, and
    SHP001 must fire with a full witness chain."""
    dst = _mutated_tree(
        tmp_path, "serving/engine.py",
        "n = len(packed)\n        rb = _bucket(n, self.max_num_seqs, minimum=1)",
        "n = len(packed)\n        rb = len(reqs)")
    findings, _ = run_paths([dst])
    hits = [f for f in findings if f.rule == "SHP001" and not f.suppressed]
    assert hits, "debucketed engine row sizing escaped the taint pass"
    assert all(f.taint_chain for f in hits)
    assert any("len(reqs)" in f.taint_chain[0] for f in hits)


def test_planted_encoder_warmup_removal_is_caught_as_shp002(tmp_path):
    """Rename the encoder's warmup: the class then runs its bucketed
    embed dispatches with no warmup routine — the exact in-tree bug this
    pass found — and SHP002 must flag the class."""
    dst = _mutated_tree(
        tmp_path, "embedding.py",
        "def warmup(self) -> int:",
        "def _prime_ladder(self) -> int:")
    findings, _ = run_paths([dst])
    hits = [f for f in findings if f.rule == "SHP002" and not f.suppressed]
    assert any("JaxBertTextEncoder" in f.message for f in hits), (
        "warmup removal on JaxBertTextEncoder escaped SHP002")


def test_planted_pipeline_dropped_tp_reduce_is_caught_as_spd003(tmp_path):
    """Drop the Megatron row-parallel psum from the pp training body: the
    tp-partitioned layer inputs then leave the shard_map with no reduction
    over tp under a replicated out_specs, and SPD003 must fire with the
    in_specs -> no-reduction -> out_specs witness."""
    dst = _mutated_tree(
        tmp_path, "training/pipeline.py",
        'reduce = (lambda x: lax.psum(x, "tp")) if tp > 1 else None',
        "reduce = None")
    findings, _ = run_paths([dst])
    hits = [f for f in findings if f.rule == "SPD003" and not f.suppressed]
    assert hits, "dropped tp reduce in pp_loss escaped the SPMD pass"
    (hit,) = hits
    assert "'tp'" in hit.message
    assert hit.taint_chain and len(hit.taint_chain) >= 3
    assert "in_specs" in hit.taint_chain[0]
    assert "pp_loss" in hit.taint_chain[1]
    assert "out_specs" in hit.taint_chain[-1]


def test_planted_ring_perm_without_modulo_is_caught_as_spd004(tmp_path):
    """Strip the % axis_size wrap from the ring-attention rotation: the
    last rank's destination falls off the ring, and SPD004 must anchor the
    finding at each ppermute with the perm-build step in the witness."""
    dst = _mutated_tree(
        tmp_path, "parallel/ring_attention.py",
        "perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]",
        "perm = [(j, j + 1) for j in range(axis_size)]")
    findings, _ = run_paths([dst])
    hits = [f for f in findings if f.rule == "SPD004" and not f.suppressed]
    assert hits, "unwrapped ring perm escaped the SPMD pass"
    assert all("% axis_size" in f.message for f in hits)
    for f in hits:
        assert f.taint_chain and "perm built here" in f.taint_chain[0]
        assert "ring_attention.py:67" in f.taint_chain[0]


def test_planted_donated_page_reread_is_caught_as_spd002(tmp_path):
    """Stop rebinding the scatter_pages result on the fault-in path: the
    donated device page pools are then re-read on the next loop pass, and
    SPD002 must carry the donate-site -> stale-read witness."""
    dst = _mutated_tree(
        tmp_path, "serving/engine.py",
        "(self._k_pages, self._v_pages, self._k_scales,\n"
        "             self._v_scales) = scatter_pages(\n"
        "                self._k_pages, self._v_pages, idx_d,",
        "_ = scatter_pages(\n"
        "                self._k_pages, self._v_pages, idx_d,")
    findings, _ = run_paths([dst])
    hits = [f for f in findings if f.rule == "SPD002" and not f.suppressed]
    assert hits, "donated page-pool re-read escaped the SPMD pass"
    assert any("self._k_pages" in f.message for f in hits)
    for f in hits:
        assert f.taint_chain
        assert "scatter_pages" in f.taint_chain[0]
        assert "donate position" in f.taint_chain[0]
        assert "read again" in f.taint_chain[-1]


def test_wpa004_positive_catches_both_leak_and_double_free():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_pos"])
    messages = [f.message for f in findings if f.rule == "WPA004"]
    assert any("leak" in m for m in messages), messages
    assert any("double-free" in m for m in messages), messages


# KV tiering extends the WPA004 alphabet: evict()/fault_in() move pages
# between the device and host tiers WITHOUT changing ownership, so the
# checker must (a) not treat a tier move as a release — parking pages on
# the host and dropping the handle is still a leak — and (b) flag a tier
# move applied to a handle whose pages were already released.

def test_wpa004_tier_positive_catches_use_after_release_and_leak():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_tier_pos"])
    messages = [f.message for f in findings if f.rule == "WPA004"]
    assert any("use-after-release" in m for m in messages), messages
    # evict() must NOT count as a release: the parked handle still leaks
    assert any("leak" in m for m in messages), messages


def test_wpa004_tier_negative_is_silent():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_tier_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_wpa004_tier_suppressed_is_silenced_with_justification():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_tier_sup"])
    hits = [f for f in findings if f.rule == "WPA004"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


# Preemption extends the WPA004 alphabet once more: park() surrenders a
# victim's pages to the host tier but keeps the handle accountable — it
# must later be resumed (ownership returns) or released (deadline reap).
# Dropping a parked handle strands host-tier pages forever; parking or
# resuming a released handle is a use-after-release.

def test_wpa004_park_positive_catches_leak_and_use_after_release():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_park_pos"])
    messages = [f.message for f in findings if f.rule == "WPA004"]
    assert any("parked page leak" in m for m in messages), messages
    assert any("use-after-release" in m for m in messages), messages


def test_wpa004_park_negative_is_silent():
    # both legal closes: park -> resume -> release, and park -> release
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_park_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_wpa004_park_suppressed_is_silenced_with_justification():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_park_sup"])
    hits = [f for f in findings if f.rule == "WPA004"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


# int4 KV pages sharpen the WPA004 reap path: both nibble planes of an
# int4 pool live in ONE set of page handles (serving/kv_cache.py packs
# k's halves into the same uint8 page), so a reap sweep that frees "per
# plane" double-frees, and clearing the per-page scale table without
# releasing the pages strands them forever.

def test_wpa004_reap_positive_catches_per_plane_double_free_and_leak():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_reap_pos"])
    messages = [f.message for f in findings if f.rule == "WPA004"]
    assert any("double-free" in m for m in messages), messages
    assert any("leak" in m for m in messages), messages


def test_wpa004_reap_negative_is_silent():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_reap_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_wpa004_reap_suppressed_is_silenced_with_justification():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_reap_sup"])
    hits = [f for f in findings if f.rule == "WPA004"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


# Disaggregated serving extends the WPA004 alphabet again: export_pages()
# puts a handle in flight toward a peer pool and import_pages() lands it.
# The checker must prove every export reaches exactly one import or a
# release — dangling exports, double-imports, and transfers of released
# handles all fire; the clean handoff (and the abandon path) stay silent.

def test_wpa004_xfer_positive_catches_all_three_shapes():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_xfer_pos"])
    messages = [f.message for f in findings if f.rule == "WPA004"]
    assert any("dangling export" in m for m in messages), messages
    assert any("double-import" in m for m in messages), messages
    assert any("use-after-release" in m for m in messages), messages


def test_wpa004_xfer_negative_is_silent():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_xfer_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_wpa004_xfer_suppressed_is_silenced_with_justification():
    findings, _ = run_paths([WPA_FIXTURES / "wpa004_xfer_sup"])
    hits = [f for f in findings if f.rule == "WPA004"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


# The fleet router reads per-replica chain digests the driver thread
# updates every step (serving/routing.py).  These fixtures pin the exact
# cross-domain shape: an event-loop pick path consuming a driver-written
# digest attribute must go through a lock (or a justified atomic swap).

def test_wpa002_router_digest_read_without_lock_fires():
    findings, _ = run_paths([WPA_FIXTURES / "wpa002_router_pos"])
    hits = [f for f in findings if f.rule == "WPA002" and not f.suppressed]
    assert hits, "lock-free cross-domain digest read escaped WPA002"
    assert any("resident" in f.message for f in hits), \
        [f.message for f in hits]


def test_wpa002_router_locked_digest_swap_is_silent():
    findings, _ = run_paths([WPA_FIXTURES / "wpa002_router_neg"])
    assert findings == [], [(f.rule, f.line, f.message) for f in findings]


def test_wpa002_router_suppressed_swap_needs_justification():
    findings, _ = run_paths([WPA_FIXTURES / "wpa002_router_sup"])
    hits = [f for f in findings if f.rule == "WPA002"]
    assert hits, "suppressed variant should still produce (suppressed) findings"
    assert all(f.suppressed and f.justification for f in hits)


def test_domain_annotation_seeds_inference(tmp_path):
    # `# tpulint: domain=event_loop` pins a sync helper to the loop even
    # with no call edge proving it — the annotation is the seed
    (tmp_path / "mod.py").write_text(
        "import time\n\n\n"
        "# tpulint: domain=event_loop\n"
        "def helper():\n"
        "    time.sleep(1)\n"
    )
    findings, _ = run_paths([tmp_path])
    assert [f.rule for f in findings] == ["WPA001"]


def test_tpu003_fires_on_unbucketed_search_fixture():
    # the hazard retrieval/device_index.py's bucket contract exists to
    # prevent: corpus/query counts flowing straight into jitted shapes
    findings = analyze_file(FIXTURES / "tpu003_search_unbucketed_pos.py")
    hits = [f for f in findings if f.rule == "TPU003"]
    assert len(hits) >= 2  # traced shape AND len()-into-jit both caught
    assert all(not f.suppressed for f in hits)
    assert [f.rule for f in findings] == ["TPU003"] * len(findings)


# -------------------------------------------------------------- suppressions

def test_justified_suppression_silences_and_records_reason():
    findings = analyze_file(FIXTURES / "suppress_ok.py")
    assert findings, "fixture should produce (suppressed) findings"
    assert all(f.suppressed for f in findings)
    assert all(f.justification for f in findings)


def test_suppression_without_justification_keeps_finding_and_adds_lnt000():
    findings = analyze_file(FIXTURES / "suppress_nojust.py")
    rules = {f.rule for f in findings}
    assert RULE_NO_JUSTIFICATION in rules
    asy = [f for f in findings if f.rule == "ASY001"]
    assert asy and not asy[0].suppressed


def test_unknown_rule_in_suppression_is_reported():
    findings = analyze_file(FIXTURES / "suppress_unknown.py")
    assert RULE_UNKNOWN_RULE in {f.rule for f in findings}


def test_stale_suppression_is_swept(tmp_path):
    # a justified directive matching zero findings is dead weight that
    # would silently swallow the next real finding on that line
    (tmp_path / "mod.py").write_text(
        "import time\n\n\n"
        "def fine():\n"
        "    # tpulint: disable=ASY001 -- historical; the async wrapper was removed\n"
        "    return time.monotonic()\n"
    )
    findings, _ = run_paths([tmp_path])
    assert [f.rule for f in findings] == [RULE_STALE_SUPPRESSION]
    assert not findings[0].suppressed


def test_used_suppression_is_not_swept():
    findings, _ = run_paths([FIXTURES / "suppress_ok.py"])
    assert RULE_STALE_SUPPRESSION not in {f.rule for f in findings}


def test_directive_inside_string_literal_is_ignored():
    src = 'MSG = "# tpulint: disable=ASY001 -- not a real comment"\n'
    assert analyze_source(src, "s.py") == []


def test_parse_error_becomes_a_finding_not_a_crash():
    findings = analyze_source("def broken(:\n", "broken.py")
    assert [f.rule for f in findings] == [RULE_PARSE_ERROR]


# ----------------------------------------------------------------- reporters

def test_json_reporter_schema():
    findings, stats = run_paths([FIXTURES / "asy001_pos.py"])
    payload = json.loads(render_json(findings, stats))
    assert payload["version"] == 4
    assert set(payload["stats"]) == {"files", "findings", "unsuppressed",
                                     "suppressed", "baselined",
                                     "pass_seconds"}
    assert payload["stats"]["files"] == 1
    assert payload["stats"]["unsuppressed"] == len(payload["findings"]) > 0
    for entry in payload["findings"]:
        assert set(entry) == {"path", "line", "col", "rule", "message",
                              "suppressed", "justification", "qualname",
                              "baselined", "witness"}
        assert entry["rule"] in RULE_IDS
        assert entry["qualname"]  # every finding is attributed to a scope
    assert set(payload["rules"]) == set(ALL_RULE_IDS)


def test_json_stats_report_per_pass_wall_time():
    """v4 surfaces where the lint budget goes: one graph build shared by
    the wpa/shapeflow/spmdflow passes, each timed separately."""
    findings, stats = run_paths([SPD_FIXTURES / "spd001_pos"])
    seconds = stats["pass_seconds"]
    assert set(seconds) == {"graph_build", "per_file", "wpa",
                            "shapeflow", "spmdflow"}
    assert all(isinstance(v, float) and v >= 0.0 for v in seconds.values())


def test_json_reporter_carries_witness_for_shp001_and_spd002():
    findings, stats = run_paths([SHP_FIXTURES / "shp001_pos"])
    payload = json.loads(render_json(findings, stats))
    (entry,) = [e for e in payload["findings"] if e["rule"] == "SHP001"]
    assert isinstance(entry["witness"], list) and len(entry["witness"]) >= 3
    findings, stats = run_paths([SPD_FIXTURES / "spd002_pos"])
    payload = json.loads(render_json(findings, stats))
    entries = [e for e in payload["findings"] if e["rule"] == "SPD002"]
    assert entries and all(isinstance(e["witness"], list) for e in entries)


def test_sarif_reporter_schema():
    """The SARIF output must be structurally valid 2.1.0: versioned, one
    run, every result tied to a registered rule with a physical location,
    and suppressed findings carried as SARIF suppressions (not dropped)."""
    findings, stats = run_paths([SHP_FIXTURES / "shp001_pos",
                                 SHP_FIXTURES / "shp003_sup"])
    payload = json.loads(render_sarif(findings, stats))
    assert payload["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in payload["$schema"]
    (run,) = payload["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "tpulint"
    rule_ids = [r["id"] for r in driver["rules"]]
    assert rule_ids == sorted(rule_ids) and set(rule_ids) == set(ALL_RULE_IDS)
    for rule in driver["rules"]:
        assert rule["shortDescription"]["text"]
        assert rule["fullDescription"]["text"]
    assert run["results"], "expected results for the positive fixtures"
    for result in run["results"]:
        assert result["ruleId"] in ALL_RULE_IDS
        assert driver["rules"][result["ruleIndex"]]["id"] == result["ruleId"]
        loc = result["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith(".py")
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1
    by_rule = {r["ruleId"]: r for r in run["results"]}
    # SHP001's witness rides in the message text
    assert "witness chain:" in by_rule["SHP001"]["message"]["text"]
    assert "suppressions" not in by_rule["SHP001"]
    sup = by_rule["SHP003"]["suppressions"][0]
    assert sup["kind"] == "inSource" and sup["justification"]
    assert run["properties"]["stats"]["suppressed"] == 1


def test_ci_artifact_schema_gate(tmp_path):
    """The exact gate scripts/ci.sh runs over artifacts/tpulint.{json,sarif}:
    generate both artifacts from a fixture package, pass them through
    scripts/check_tpulint_schema.py, and prove the checker rejects drift."""
    findings, stats = run_paths([SPD_FIXTURES / "spd002_pos"])
    json_path = tmp_path / "tpulint.json"
    sarif_path = tmp_path / "tpulint.sarif"
    json_path.write_text(render_json(findings, stats))
    sarif_path.write_text(render_sarif(findings, stats))
    proc = subprocess.run(
        [sys.executable, "scripts/check_tpulint_schema.py",
         str(json_path), str(sarif_path)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout
    # drift in the pinned version must fail the gate
    payload = json.loads(json_path.read_text())
    payload["version"] = 3
    json_path.write_text(json.dumps(payload))
    proc = subprocess.run(
        [sys.executable, "scripts/check_tpulint_schema.py",
         str(json_path), str(sarif_path)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "version" in proc.stderr


def test_text_reporter_lists_location_and_rule():
    findings, stats = run_paths([FIXTURES / "tpu001_pos.py"])
    text = render_text(findings, stats)
    assert "tpu001_pos.py" in text and "TPU001" in text
    assert "finding(s)" in text.splitlines()[-1]


# ----------------------------------------------------------------- discovery

def test_iter_py_files_exclude():
    all_files = list(iter_py_files([FIXTURES]))
    assert any(p.name == "tpu001_pos.py" for p in all_files)
    none = list(iter_py_files([FIXTURES], excludes=["lint_fixtures"]))
    assert none == []


# ----------------------------------------------------------------------- CLI

def _run_cli(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "tools.tpulint", *args],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )


def test_cli_exit_codes():
    assert _run_cli("tests/lint_fixtures/tpu001_pos.py").returncode == 1
    assert _run_cli("tests/lint_fixtures/tpu001_neg.py").returncode == 0
    assert _run_cli().returncode == 2  # no paths


def test_cli_json_output_parses():
    proc = _run_cli("tests/lint_fixtures/tpu006_pos.py", "--format", "json")
    payload = json.loads(proc.stdout)
    assert payload["findings"][0]["rule"] == "TPU006"


def test_cli_unknown_suppression_rule_gets_its_own_exit_code():
    # a misspelled rule id silences nothing; exit 3 makes CI fail loudly
    # instead of quietly un-suppressing
    assert _run_cli("tests/lint_fixtures/suppress_unknown.py").returncode == 3


def test_cli_sarif_output_parses():
    proc = _run_cli("tests/lint_fixtures/tpu006_pos.py", "--format", "sarif")
    payload = json.loads(proc.stdout)
    assert payload["version"] == "2.1.0"
    assert payload["runs"][0]["results"][0]["ruleId"] == "TPU006"


# ----------------------------------------------------------------- diff mode

def test_diff_closure_follows_reverse_dependencies():
    """A changed util must pull in its (transitive) importers — they are
    where a cross-module regression would surface — but not the modules it
    merely imports."""
    from tools.tpulint import diffmode

    entries = [
        ("pkg/__init__.py", ""),
        ("pkg/util.py", "def bucket(n):\n    return n\n"),
        ("pkg/engine.py", "from pkg.util import bucket\n"),
        ("pkg/api.py", "from pkg.engine import run\n"),
        ("pkg/other.py", "VALUE = 1\n"),
    ]
    real = diffmode.changed_files
    diffmode.changed_files = lambda ref: {"pkg/util.py"}
    try:
        closure = diffmode.diff_closure(entries, "HEAD")
    finally:
        diffmode.changed_files = real
    assert closure == {"pkg/util.py", "pkg/engine.py", "pkg/api.py"}


def test_diff_mode_scopes_findings_to_the_closure(monkeypatch):
    """Whole-program analysis still sees every file (no fabricated or lost
    cross-module facts), but only closure files report findings: changing
    the taint SOURCE module reports nothing (the sink file is out of
    scope), while changing the SINK module reports the cross-module
    SHP001."""
    from tools.tpulint import diffmode

    pkg = SHP_FIXTURES / "shp001_pos"
    serving = str(pkg / "serving.py").replace("\\", "/")
    shapes = str(pkg / "shapes.py").replace("\\", "/")

    monkeypatch.setattr(diffmode, "changed_files", lambda ref: {serving})
    findings, stats = run_paths([pkg], diff_base="HEAD")
    assert stats["diff_selected"] == 1
    assert findings == []  # the SHP001 anchors in shapes.py, out of scope

    monkeypatch.setattr(diffmode, "changed_files", lambda ref: {shapes})
    findings, stats = run_paths([pkg], diff_base="HEAD")
    # shapes.py changed; serving.py imports it, so both are in scope
    assert stats["diff_selected"] == 2
    assert [f.rule for f in findings] == ["SHP001"]


def test_cli_diff_with_bad_ref_is_a_usage_error():
    proc = _run_cli("tests/lint_fixtures/tpu001_neg.py",
                    "--diff", "no-such-ref-xyzzy")
    assert proc.returncode == 2
    assert "--diff" in proc.stderr


def test_cli_diff_reports_scope_in_stats():
    proc = _run_cli("tests/lint_fixtures/tpu001_neg.py", "--diff", "HEAD",
                    "--format", "json")
    assert proc.returncode in (0, 1)
    payload = json.loads(proc.stdout)
    assert isinstance(payload["stats"]["diff_selected"], int)


# ------------------------------------------------------------------ baseline

def test_baseline_roundtrip(tmp_path):
    baseline = tmp_path / "baseline.json"
    target = "tests/lint_fixtures/wpa/wpa001_pos"
    # without a baseline the positive fixture fails the run
    assert _run_cli(target).returncode == 1
    # write-baseline records the fingerprints and exits clean
    assert _run_cli(target, "--write-baseline", str(baseline)).returncode == 0
    payload = json.loads(baseline.read_text())
    assert payload["version"] == 1 and payload["fingerprints"]
    # rule+path+qualname, line-insensitive: no line numbers in fingerprints
    assert all(fp.count("::") == 2 for fp in payload["fingerprints"])
    # the same findings are now baselined and no longer fail CI
    proc = _run_cli(target, "--baseline", str(baseline), "--format", "json")
    assert proc.returncode == 0
    out = json.loads(proc.stdout)
    assert out["stats"]["baselined"] > 0
    assert all(f["baselined"] for f in out["findings"] if not f["suppressed"])
    # a NEW finding (different qualname) still fails against the old baseline
    assert _run_cli(target, "tests/lint_fixtures/tpu001_pos.py",
                    "--baseline", str(baseline)).returncode == 1


def test_committed_baseline_is_empty():
    """The acceptance bar: the tree carries justified suppressions, not
    baselined debt."""
    payload = json.loads((REPO / "tools" / "tpulint" / "baseline.json").read_text())
    assert payload == {"version": 1, "fingerprints": []}




# ---------------------------------------------------- the tree stays clean

@pytest.fixture(scope="module")
def tree_run():
    """One timed full-tree run (per-file + whole-program pass) shared by
    the self-check and the wall-time budget test."""
    import time as _time

    start = _time.monotonic()
    findings, stats = run_paths(
        [REPO / "githubrepostorag_tpu", REPO / "tests"],
        excludes=["tests/lint_fixtures"],
    )
    return findings, stats, _time.monotonic() - start


def test_production_tree_has_zero_unsuppressed_findings(tree_run):
    """The same gate `make lint` enforces, kept inside tier-1 so a finding
    fails CI even when only pytest runs — now including the WPA
    whole-program rules over githubrepostorag_tpu itself."""
    findings, stats, _ = tree_run
    unsuppressed = [f for f in findings if not f.suppressed]
    assert unsuppressed == [], [f"{f.location()} {f.rule} {f.message}" for f in unsuppressed]
    # and every suppression that does exist must carry a justification
    for f in findings:
        if f.suppressed:
            assert f.justification


def test_production_tree_exercises_the_wpa_pass(tree_run):
    """Guard against the whole-program pass silently skipping the tree:
    the engine's allocator discipline must keep it suppression-visible."""
    findings, _, _ = tree_run
    wpa_suppressed = [f for f in findings
                      if f.rule.startswith("WPA") and f.suppressed]
    assert wpa_suppressed, "expected justified WPA suppressions in-tree"


def test_lint_wall_time_budget(tree_run):
    """The whole-program pass must not rot CI: a full-tree run stays
    under 30 s (the `make lint` budget)."""
    _, _, elapsed = tree_run
    assert elapsed < 30.0, f"full-tree lint took {elapsed:.1f}s (budget 30s)"
