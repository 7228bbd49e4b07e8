"""Tests for the `repro.analysis` static-analysis suite.

Three layers:
  * fixture corpora under tests/fixtures/analysis/ — every rule id fires
    on its planted violation and stays silent on the good counterpart;
  * mutation sensitivity — copies of the clean corpus with fields.py,
    the arbiter module, or the doc table perturbed must fail the
    bitfield pass (the acceptance criterion that the pass truly derives
    its table from all three sources);
  * the real repo — `run_passes` over this checkout returns zero
    findings, and the CLI exit codes match.
"""
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import RepoContext, list_passes, run_passes
from repro.analysis.core import RULE_ID_RE, scan_pragmas

HERE = Path(__file__).resolve().parent
REPO_ROOT = HERE.parent
FIXTURES = HERE / "fixtures" / "analysis"
CLI = REPO_ROOT / "tools" / "check_contract.py"

#: every rule badrepo plants (BF101-BF104 need a malformed fields.py and
#: live in the badfields_* corpora instead)
BADREPO_RULES = {
    "BF105", "BF106",
    "DT201", "DT202", "DT203", "DT204", "DT205",
    "PP301", "PP302", "PP303",
    "RC401", "RC402", "RC403", "RC404", "RC405", "RC406", "RC407",
    "PL501", "PL502", "PL503", "PL505",
    "CM601", "CM602",
}


def rules_of(root, passes=None):
    return {f.rule for f in run_passes(RepoContext(root), passes).findings}


# ---------------------------------------------------------------- catalog

def test_pass_catalog():
    infos = list_passes()
    assert {i.name for i in infos} == {
        "bitfield", "dtype", "policy-purity", "registry-coverage",
        "pallas-lint", "commands"}
    all_rules = [rid for i in infos for rid, _ in i.rules]
    assert len(all_rules) == len(set(all_rules)), "rule ids must be unique"
    assert all(RULE_ID_RE.match(r) for r in all_rules)
    declared = set(all_rules)
    assert BADREPO_RULES | {"BF101", "BF102", "BF103", "BF104"} == declared


# ---------------------------------------------------------------- corpora

def test_goodrepo_is_clean():
    res = run_passes(RepoContext(FIXTURES / "goodrepo"))
    assert res.findings == []


def test_badrepo_fails_and_fires_every_plantable_rule():
    res = run_passes(RepoContext(FIXTURES / "badrepo"))
    assert not res.ok
    assert {f.rule for f in res.findings} == BADREPO_RULES


@pytest.mark.parametrize("corpus,rule", [
    ("badfields_missing", "BF101"),
    ("badfields_overlap", "BF102"),
    ("badfields_order", "BF103"),
    ("badfields_width", "BF104"),
])
def test_malformed_fields_corpora(corpus, rule):
    fired = rules_of(FIXTURES / corpus, ["bitfield"])
    assert rule in fired
    # and the clean corpus never trips this rule
    assert rule not in rules_of(FIXTURES / "goodrepo", ["bitfield"])


@pytest.mark.parametrize("rule", sorted(BADREPO_RULES))
def test_each_rule_has_good_and_bad_instance(rule):
    assert rule in rules_of(FIXTURES / "badrepo")
    assert rule not in rules_of(FIXTURES / "goodrepo")


# ------------------------------------------------------------ suppression

def test_pragma_suppression_applies_to_next_line():
    res = run_passes(RepoContext(FIXTURES / "badrepo"), ["dtype"])
    suppressed = {(f.path, f.line) for f, _ in res.suppressed}
    engine = "src/repro/core/sweep/engine.py"
    assert any(p == engine for p, _ in suppressed)
    # the suppressed site never shows up as a finding
    assert not (set((f.path, f.line) for f in res.findings) & suppressed)
    # and the pragma carries its justification
    (_, pragma), = [s for s in res.suppressed if s[0].path == engine]
    assert "pragma suppression" in pragma.reason


def test_pragma_parser():
    text = ("x = 1  # contract: disable=DT201 -- inline reason\n"
            "# contract: disable=BF105,PL501 -- standalone covers next\n"
            "y = 2\n")
    pragmas = scan_pragmas(text, "f.py")
    assert pragmas[0].rules == ("DT201",) and pragmas[0].covers == (1,)
    assert pragmas[1].rules == ("BF105", "PL501")
    assert pragmas[1].covers == (2, 3)
    assert pragmas[1].reason == "standalone covers next"


# ---------------------------------------------------- mutation sensitivity

def _mutated_goodrepo(tmp_path, mutate):
    root = tmp_path / "repo"
    shutil.copytree(FIXTURES / "goodrepo", root)
    mutate(root)
    return root


def test_bitfield_catches_fields_mutation(tmp_path):
    def mutate(root):
        f = root / "src/repro/core/sweep/fields.py"
        f.write_text(f.read_text().replace("AGE_BITS = 20", "AGE_BITS = 19"))

    root = _mutated_goodrepo(tmp_path, mutate)
    fired = rules_of(root, ["bitfield"])
    assert "BF106" in fired  # consumers follow the import; the doc cannot


def test_bitfield_catches_arbiter_mutation(tmp_path):
    def mutate(root):
        f = root / "src/repro/core/sweep/arbiter.py"
        f.write_text(f.read_text() + "\nW_HIT = 1 << 20\n")

    root = _mutated_goodrepo(tmp_path, mutate)
    assert "BF105" in rules_of(root, ["bitfield"])


def test_bitfield_catches_doc_mutation(tmp_path):
    def mutate(root):
        f = root / "docs/tick-contract.md"
        f.write_text(f.read_text().replace("`W_HIT = 1 << 21`",
                                           "`W_HIT = 1 << 22`"))

    root = _mutated_goodrepo(tmp_path, mutate)
    assert "BF106" in rules_of(root, ["bitfield"])


def test_bitfield_catches_noconf_mutation(tmp_path):
    # the subarray no-conflict bit is part of the packed contract: moving
    # it onto the hit flag must trip the layout check in every consumer
    def mutate(root):
        f = root / "src/repro/core/sweep/fields.py"
        f.write_text(f.read_text().replace("NOCONF_SHIFT = 20",
                                           "NOCONF_SHIFT = 21"))

    root = _mutated_goodrepo(tmp_path, mutate)
    fired = rules_of(root, ["bitfield"])
    # the duplicate shift both overlaps the hit flag and breaks priority
    assert fired == {"BF102", "BF103"}


def test_commands_catches_doc_table_drift(tmp_path):
    # dropping a mnemonic row from the doc must trip CM601; renaming it
    # to something the code never emits must also trip CM602
    def mutate(root):
        f = root / "docs/tick-contract.md"
        f.write_text(f.read_text().replace("| `REF_PB` | bank  |",
                                           "| `REF_SB` | bank  |"))

    root = _mutated_goodrepo(tmp_path, mutate)
    fired = rules_of(root, ["commands"])
    assert fired == {"CM601", "CM602"}


def test_commands_catches_new_code_mnemonic(tmp_path):
    # the pass re-derives the tuple by AST: a new command the doc does
    # not yet table must fail CI
    def mutate(root):
        f = root / "src/repro/core/commands/trace.py"
        f.write_text(f.read_text().replace(
            '"REF_AB", "REF_PB")', '"REF_AB", "REF_PB", "SRE")'))

    root = _mutated_goodrepo(tmp_path, mutate)
    assert rules_of(root, ["commands"]) == {"CM601"}


def test_pallas_lint_catches_dropped_state_plane(tmp_path):
    # PL505's reason to exist: dropping a plane from the fused body's
    # return dict freezes it with no runtime error anywhere
    def mutate(root):
        f = root / "src/repro/core/sweep/jaxbody.py"
        f.write_text(f.read_text().replace(
            "finish=finish, wbuf=wbuf)", "finish=finish)"))

    root = _mutated_goodrepo(tmp_path, mutate)
    assert "PL505" in rules_of(root, ["pallas-lint"])


def test_pallas_lint_reads_planes_only_some_grids_carry():
    # a plane only some grids carry (`pb_next`, where a DRAM states a
    # per-bank issue rule) rides in a dict-literal `**` splat: PL505
    # still sees it, and fires when a body drops it
    import ast

    from repro.analysis.passes.pallas_lint import check_state_keysets
    src = (REPO_ROOT / "src/repro/core/sweep/jaxbody.py").read_text()
    assert check_state_keysets(ast.parse(src), "jaxbody.py") == []
    splat = '**({"pb_next": pb_next} if cfg.pb_rules else {}),'
    assert src.count(splat) == 2          # the open and closed bodies
    fired = check_state_keysets(ast.parse(src.replace(splat, "", 1)),
                                "jaxbody.py")
    assert [f.rule for f in fired] == ["PL505"]
    assert "'pb_next'" in fired[0].message


def test_registry_catches_sarp_policy_skipping_subarray_matrix(tmp_path):
    # RC406's reason to exist: a new SARP-trait registration (lambda
    # keyword spelling) that never reaches the subarray matrix
    def mutate(root):
        f = root / "src/repro/core/policy/paper.py"
        f.write_text(f.read_text() + (
            "\nregister_policy(\"stealth_sarp\",\n"
            "                lambda **kw: SarpPolicy(sarp=True, **kw))\n"))
        t = root / "tests/test_subarray.py"
        t.write_text('"""Static matrix without the newcomer."""\n'
                     'POLICIES = ("sarp_pb", "dsarp")\n')

    root = _mutated_goodrepo(tmp_path, mutate)
    fired = rules_of(root, ["registry-coverage"])
    assert "RC406" in fired


def test_registry_catches_serving_scenario_skipping_cosim_matrix(tmp_path):
    # RC407's reason to exist: a new register_serving_scenario that the
    # co-sim matrix never replays (the matrix iterates
    # list_serving_scenarios(), so the mutation also pins it to a static
    # tuple that misses the newcomer)
    def mutate(root):
        f = root / "src/repro/core/refresh/scenarios.py"
        f.write_text(f.read_text() + (
            "\n\n@register_serving_scenario(\"serving_stealth\")\n"
            "def serving_stealth(n_requests, rs):\n"
            "    return [2] * n_requests\n"))
        t = root / "tests/test_serving_cosim.py"
        t.write_text('"""Static matrix without the newcomer."""\n'
                     'COSIM_MATRIX = ("serving_fixture",)\n')

    root = _mutated_goodrepo(tmp_path, mutate)
    fired = rules_of(root, ["registry-coverage"])
    assert "RC407" in fired
    # the un-mutated corpus stays clean — the dynamic-iteration spelling
    # covers any registered name
    assert "RC407" not in rules_of(FIXTURES / "goodrepo",
                                   ["registry-coverage"])


def test_registry_catches_new_unregistered_policy(tmp_path):
    # the exact scenario the pass exists for: a new @register_policy that
    # silently skips every matrix and the fast-path table
    def mutate(root):
        f = root / "src/repro/core/policy/paper.py"
        f.write_text(f.read_text() + (
            "\n\n@register_policy(\"newcomer\")\n"
            "class NewcomerPolicy:\n"
            "    ideal = False\n"
            "    def select(self, view):\n"
            "        return []\n"))

    root = _mutated_goodrepo(tmp_path, mutate)
    fired = rules_of(root, ["registry-coverage"])
    assert {"RC402", "RC404"} <= fired  # static matrix + fast-path table


# --------------------------------------------------------------- the repo

def test_repo_is_clean():
    res = run_passes(RepoContext(REPO_ROOT))
    assert res.findings == [], "\n".join(str(f) for f in res.findings)


def test_cli_exit_codes():
    env_root = str(REPO_ROOT)
    ok = subprocess.run(
        [sys.executable, str(CLI), "--all", "--root", env_root],
        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = subprocess.run(
        [sys.executable, str(CLI), "--root",
         str(FIXTURES / "badrepo")],
        capture_output=True, text=True)
    assert bad.returncode == 1
    assert "PL501" in bad.stdout and "RC404" in bad.stdout
    listed = subprocess.run(
        [sys.executable, str(CLI), "--list"], capture_output=True,
        text=True)
    assert listed.returncode == 0 and "bitfield" in listed.stdout
    unknown = subprocess.run(
        [sys.executable, str(CLI), "--pass", "nope"], capture_output=True,
        text=True)
    assert unknown.returncode == 2
