"""kf-lint in tier-1: the tree must be clean, and the checkers must
actually catch what they claim to catch (fixtures under
tests/lint_fixtures/ seed known violations).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from kungfu_tpu.analysis import (
    aggschema,
    blockingio,
    collectives,
    envcheck,
    handlecheck,
    jitpurity,
    ledgerschema,
    lockcheck,
    protoverify,
    pylockorder,
    recompilehazard,
    retrydiscipline,
    shardaxis,
    shardspec,
    tracevocab,
    wirecontract,
)
from kungfu_tpu.analysis.cli import SHARD_CHECKERS, main as cli_main, run_checkers
from kungfu_tpu.analysis.core import repo_root

ROOT = repo_root(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "lint_fixtures")

MINI_REGISTRY = '''"""Mini env registry for lint fixtures.

=================  ===========================
``KF_SELF_SPEC``   this worker's ``host:port``
=================  ===========================
"""
'''


def _tmp_tree(tmp_path, files):
    """Build a minimal repo layout: {relpath: source or fixture name}."""
    for rel, content in files.items():
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        if os.path.exists(os.path.join(FIXTURES, str(content))):
            shutil.copy(os.path.join(FIXTURES, str(content)), dst)
        else:
            dst.write_text(content)
    return str(tmp_path)


@pytest.fixture(scope="module")
def tree_run():
    """ONE in-process run of every checker over the real tree, from a
    cold parse cache, shared by the tree-wide tests (a full run costs
    ~10 s): its violations and its per-file parse counts."""
    from kungfu_tpu.analysis import core

    core.clear_parse_cache()
    violations = run_checkers(ROOT)
    return violations, dict(core.PARSE_COUNTS)


class TestTreeIsClean:
    def test_all_checkers_clean_on_tree(self, tree_run):
        """THE tier-1 gate: every project invariant holds on every run."""
        violations, _ = tree_run
        assert violations == [], "\n".join(v.render() for v in violations)

    def test_cli_exit_zero_on_tree(self):
        rc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "kflint")],
            capture_output=True, timeout=120,
        )
        assert rc.returncode == 0, rc.stdout.decode() + rc.stderr.decode()


class TestJitPurity:
    def test_fixture_violations_caught(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "jit_sync_bad.py"})
        got = {(v.line, v.message.split(": ", 1)[1]) for v in jitpurity.check(root)}
        lines = {line for line, _ in got}
        assert lines == {11, 12, 13, 14, 15, 22, 31, 43}, sorted(got)
        # the suppressed .item() (line 17) must NOT appear
        assert all("allow" not in m for _, m in got)

    def test_one_level_deep_attribution(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "jit_sync_bad.py"})
        deep = [v for v in jitpurity.check(root) if v.line == 22]
        assert len(deep) == 1
        assert "called from jitted bad_step" in deep[0].message


class TestBlockingIO:
    def test_fixture_violations_caught(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "blocking_io_bad.py"})
        lines = sorted(v.line for v in blockingio.check(root))
        assert lines == [14, 18, 23, 31, 32, 39], lines

    def test_non_threaded_module_out_of_scope(self, tmp_path):
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import urllib.request\n"
                "data = urllib.request.urlopen('http://x')\n",
        })
        assert blockingio.check(root) == []


class TestLockDiscipline:
    def test_fixture_violations_caught(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/native/bad.cpp": "lock_bad.cpp"})
        got = sorted((v.line, v.message.split(" ")[2].strip("`"))
                     for v in lockcheck.check(root))
        assert [line for line, _ in got] == [21, 22, 27, 37], got

    def test_wrong_mutex_is_reported(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/native/bad.cpp": "lock_bad.cpp"})
        wrong = [v for v in lockcheck.check(root) if v.line == 27]
        assert wrong and "other_mu_" in wrong[0].message


class TestRetryDiscipline:
    """The shipped bug shapes — the constant-period config-server hammer
    and hot retry loops — must be flagged; bounded, jittered,
    exponentially-backed-off loops must not."""

    def _violations(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "retry_bad.py"})
        return retrydiscipline.check(root)

    def test_fixture_violations_caught(self, tmp_path):
        got = sorted((v.line, v.message) for v in self._violations(tmp_path))
        assert [line for line, _ in got] == [14, 18, 26, 31], got
        assert "unbounded" in got[0][1]
        assert "constant period" in got[1][1]
        assert "constant period" in got[2][1]
        assert "no backoff" in got[3][1]

    def test_compliant_loops_not_flagged(self, tmp_path):
        flagged = {v.line for v in self._violations(tmp_path)}
        # good_deadline_backoff / good_attempt_ladder / good_jittered_poll
        # / per-target iteration start past the suppressed block
        assert not any(line > 45 for line in flagged), flagged

    def test_suppression_honored(self, tmp_path):
        # the allow() lines (39-45) carry a waived unbounded loop and a
        # waived constant sleep — neither may surface
        flagged = {v.line for v in self._violations(tmp_path)}
        assert not any(38 <= line <= 46 for line in flagged), flagged


class TestHandleDiscipline:
    """kf-overlap's lifetime rule: every ``*_async`` handle is waited on
    every control-flow path, never dropped, and never held across a
    membership-change entry point."""

    def _violations(self, tmp_path, fixture):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": fixture})
        return handlecheck.check(root)

    def test_bad_fixture_all_shapes_caught(self, tmp_path):
        got = sorted((v.line, v.message)
                     for v in self._violations(tmp_path, "handle_bad.py"))
        assert [line for line, _ in got] == \
            [6, 11, 17, 24, 34, 42, 48, 54, 60], got
        assert "dropped" in got[0][1]
        assert "never waited" in got[1][1]
        assert "every control-flow path" in got[2][1]
        assert "every control-flow path" in got[3][1]
        assert "elastic_step" in got[4][1]
        assert "shrink_to_survivors" in got[5][1]
        # the serving plane's membership boundary fences handles too
        assert "mark_worker_dead" in got[6][1]
        # a kf-pipeline stage re-carve is a membership boundary too: a
        # p2p handle tagged under the old stage geometry must not cross
        assert "recarve" in got[7][1]
        assert "recarve_stages_after_shrink" in got[8][1]

    def test_good_fixture_clean(self, tmp_path):
        got = self._violations(tmp_path, "handle_good.py")
        assert got == [], [v.render() for v in got]

    def test_suppression_honored(self, tmp_path):
        src = (
            "def f(engine, x):\n"
            "    engine.all_reduce_async(x)"
            "  # kflint: allow(handle-discipline)\n"
        )
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": src})
        assert handlecheck.check(root) == []

    def test_drain_is_not_an_issue_site(self, tmp_path):
        src = (
            "def f(engine):\n"
            "    engine.drain_async()\n"
            "    n = engine.drain_async(timeout=5)\n"
            "    return n\n"
        )
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": src})
        assert handlecheck.check(root) == []


class TestPersistHandleDiscipline:
    """kf-persist rides the same lifetime rule: a durable-write handle
    is an async handle — dropped/never-waited persists leak, and no
    handle (persist or collective) may straddle ``persist_fence`` /
    ``restore_from_manifest`` / ``elastic_step``."""

    def _violations(self, tmp_path, fixture):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": fixture})
        return handlecheck.check(root)

    def test_bad_fixture_all_shapes_caught(self, tmp_path):
        got = sorted((v.line, v.message)
                     for v in self._violations(tmp_path, "persist_bad.py"))
        assert [line for line, _ in got] == [6, 11, 17, 24, 30], got
        assert "dropped" in got[0][1]
        assert "never waited" in got[1][1]
        # the restore is a membership-change boundary: a persist handle
        # still in flight there may belong to the OLD geometry
        assert "restore_from_manifest" in got[2][1]
        # and the plane's own fence is a fence for EVERY handle kind —
        # a collective handle must not straddle it either
        assert "persist_fence" in got[3][1]
        assert "elastic_step" in got[4][1]

    def test_good_fixture_clean(self, tmp_path):
        got = self._violations(tmp_path, "persist_good.py")
        assert got == [], [v.render() for v in got]


class TestCollectiveConsistency:
    """The kf-verify SPMD rule: rank-conditional collectives, constant
    rendezvous-name reuse, and peer-divergent name expressions — including
    the interprocedural helper-behind-a-rank-branch shape."""

    def test_fixture_violations_caught(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "collective_bad.py"})
        got = sorted((v.line, v.message) for v in collectives.check(root))
        assert [line for line, _ in got] == [10, 21, 33, 40], got
        assert "rank-conditional branch" in got[0][1]
        assert "called only under rank-conditional branches" in got[1][1]
        assert "reused from" in got[2][1]
        assert "diverges across peers" in got[3][1]

    def test_suppression_honored(self, tmp_path):
        # waived_probe (the allow() line) must not surface
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "collective_bad.py"})
        assert all(v.line < 44 for v in collectives.check(root))

    def test_good_fixture_clean(self, tmp_path):
        """The symmetric root/leaf split, versioned names, and digest
        names — the tree's idioms — must pass untouched."""
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "collective_good.py"})
        assert collectives.check(root) == [], \
            [v.render() for v in collectives.check(root)]

    def test_comm_layer_out_of_scope(self, tmp_path):
        # the collective IMPLEMENTATION branches on rank by design
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/comm/mod.py": "collective_bad.py",
        })
        assert collectives.check(root) == []

    def test_helper_called_on_both_sides_is_balanced(self, tmp_path):
        """A helper invoked in BOTH branches of a rank split runs on
        every rank — the interprocedural rule must not flag it."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "def _announce(peer):\n"
                "    peer.channel.barrier(peer.cluster.workers,"
                " name='announce')\n\n\n"
                "def sync(peer):\n"
                "    if peer.rank() == 0:\n"
                "        _announce(peer)\n"
                "    else:\n"
                "        _announce(peer)\n",
        })
        assert collectives.check(root) == [], \
            [v.render() for v in collectives.check(root)]

    def test_literal_symmetric_split_not_reuse(self, tmp_path):
        """The compliant root/leaf split written with a literal name is
        a balanced pair, not cross-path name reuse."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "def bcast(peer, blob, workers):\n"
                "    if peer.rank() == 0:\n"
                "        peer.channel.broadcast_bytes(blob, workers,"
                " name='boot')\n"
                "        return blob\n"
                "    return peer.channel.broadcast_bytes(None, workers,"
                " name='boot')\n",
        })
        assert collectives.check(root) == [], \
            [v.render() for v in collectives.check(root)]


class TestWireContract:
    """Python framing vs C++ decoder: the real pair diffs clean, and a
    seeded one-byte mutation on EITHER side is caught (the acceptance
    criterion)."""

    def _tree(self, tmp_path, mutate_host=None, mutate_cpp=None):
        host = open(os.path.join(ROOT, "kungfu_tpu", "comm", "host.py")).read()
        cpp = open(os.path.join(ROOT, "kungfu_tpu", "native",
                                "transport.cpp")).read()
        if mutate_host:
            mutated = mutate_host(host)
            assert mutated != host, "mutation must change the file"
            host = mutated
        if mutate_cpp:
            mutated = mutate_cpp(cpp)
            assert mutated != cpp, "mutation must change the file"
            cpp = mutated
        return _tmp_tree(tmp_path, {
            "kungfu_tpu/comm/host.py": host,
            "kungfu_tpu/native/transport.cpp": cpp,
        })

    def test_real_pair_diffs_clean(self, tmp_path):
        root = self._tree(tmp_path)
        assert wirecontract.check(root) == [], \
            [v.render() for v in wirecontract.check(root)]

    def test_one_byte_python_format_mutation(self, tmp_path):
        # "<IIBH" -> "<IIBI": src_len silently widens to u32
        root = self._tree(tmp_path, mutate_host=lambda s: s.replace(
            'HEAD_FMT = "<IIBH"', 'HEAD_FMT = "<IIBI"'))
        msgs = [v.message for v in wirecontract.check(root)]
        assert any("IIBIHI" in m and "IIBHHI" in m for m in msgs), msgs

    def test_one_byte_cpp_prefix_mutation(self, tmp_path):
        # head[11] -> head[12]: the C++ fixed prefix drifts off the wire
        root = self._tree(tmp_path, mutate_cpp=lambda s: s.replace(
            "uint8_t head[11]", "uint8_t head[12]"))
        msgs = [v.message for v in wirecontract.check(root)]
        assert any("head[12]" in m for m in msgs), msgs

    def test_cpp_field_widening_caught(self, tmp_path):
        root = self._tree(tmp_path, mutate_cpp=lambda s: s.replace(
            "put_u16(out, static_cast<uint16_t>(src.size()));",
            "put_u32(out, static_cast<uint32_t>(src.size()));"))
        msgs = [v.message for v in wirecontract.check(root)]
        assert any("decode_head field sequence" in m for m in msgs), msgs

    def test_magic_drift_caught(self, tmp_path):
        root = self._tree(tmp_path, mutate_host=lambda s: s.replace(
            "0x4B465450", "0x4B465451"))
        msgs = [v.message for v in wirecontract.check(root)]
        assert any("kMagic" in m for m in msgs), msgs

    def test_codec_bypass_caught(self, tmp_path):
        """A second raw pack site inside the framing functions is exactly
        how drift starts — flagged even while still byte-identical."""
        root = self._tree(tmp_path, mutate_host=lambda s: s.replace(
            "return HeaderCodec.pack_head(token, conn_type, sb, nb, nbytes)",
            'return struct.pack("<IIBH", MAGIC, token, conn_type, len(sb))'
            ' + sb + struct.pack("<H", len(nb)) + nb'
            ' + struct.pack("<L", nbytes)'))
        msgs = [v.message for v in wirecontract.check(root)]
        assert any("bypasses HeaderCodec" in m for m in msgs), msgs

    def test_partial_tree_is_silent(self, tmp_path):
        # fixture layouts without the pair must not fail other checkers
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "env_bad.py"})
        assert wirecontract.check(root) == []

    def test_byte_identical_letter_swap_not_drift(self, tmp_path):
        """"<LLBH" packs byte-for-byte like "<IIBH" — the contract is
        width + order, so a same-width letter swap must diff clean."""
        root = self._tree(tmp_path, mutate_host=lambda s: s.replace(
            'HEAD_FMT = "<IIBH"', 'HEAD_FMT = "<LLBH"'))
        assert wirecontract.check(root) == [], \
            [v.render() for v in wirecontract.check(root)]


class TestLockOrder:
    def test_fixture_violations_caught(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "lockorder_bad.py"})
        got = sorted((v.line, v.message) for v in pylockorder.check(root))
        assert [line for line, _ in got] == [15, 33], got
        assert "lock-order cycle" in got[0][1]
        # the cycle message names both witness edges
        assert "mod.py:22" in got[0][1]
        assert "self-deadlock" in got[1][1]

    def test_good_fixture_clean(self, tmp_path):
        """Consistent global order + RLock re-entry must pass."""
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "lockorder_good.py"})
        assert pylockorder.check(root) == [], \
            [v.render() for v in pylockorder.check(root)]

    def test_release_inside_with_does_not_crash(self, tmp_path):
        """The lock-handoff pattern (explicit release() inside the with
        body) must scan clean, not crash the gate."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import threading\n\n\n"
                "class Handoff:\n"
                "    def __init__(self):\n"
                "        self.mu = threading.Lock()\n\n"
                "    def hand_over(self):\n"
                "        with self.mu:\n"
                "            self.mu.release()\n",
        })
        assert pylockorder.check(root) == [], \
            [v.render() for v in pylockorder.check(root)]


MINI_TIMELINE = (
    "EVENT_KINDS = frozenset({\n"
    '    "collective", "device", "send", "recv", "retry", "deadline",\n'
    '    "signal", "down", "shrink", "chaos", "step", "mark",\n'
    "})\n"
)


class TestTraceVocab:
    """The observability rule: span()/event() kinds must be string
    literals from timeline.py's EVENT_KINDS — a typo'd kind silently
    vanishes from every kftrace filter instead of erroring."""

    def _tree(self, tmp_path):
        return _tmp_tree(tmp_path, {
            "kungfu_tpu/monitor/timeline.py": MINI_TIMELINE,
            "kungfu_tpu/mod.py": "tracevocab_bad.py",
        })

    def test_fixture_violations_caught(self, tmp_path):
        got = sorted((v.line, v.message)
                     for v in tracevocab.check(self._tree(tmp_path)))
        assert [line for line, _ in got] == [12, 16, 21, 25], got
        assert "not in the EVENT_KINDS vocabulary" in got[0][1]
        assert "must be a string literal" in got[1][1]
        assert "without a kind argument" in got[2][1]
        assert "'shrnk'" in got[3][1]

    def test_suppression_honored(self, tmp_path):
        # the waived dynamic kind (allow line) must not surface
        flagged = {v.line for v in tracevocab.check(self._tree(tmp_path))}
        assert not any(line > 26 for line in flagged), flagged

    def test_unrelated_receivers_not_flagged(self, tmp_path):
        # Unrelated.span()/.event() calls at the fixture tail are clean
        got = tracevocab.check(self._tree(tmp_path))
        assert all("Unrelated" not in v.message for v in got)

    def test_vocab_parsed_from_real_tree(self, tmp_path):
        from kungfu_tpu.analysis.tracevocab import _vocabulary
        from kungfu_tpu.monitor.timeline import EVENT_KINDS

        assert _vocabulary(ROOT) == set(EVENT_KINDS)

    def test_no_timeline_module_is_silent(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "tracevocab_bad.py"})
        assert tracevocab.check(root) == []


MINI_AGGREGATOR = (
    "SNAPSHOT_FIELDS = frozenset({\n"
    '    "kfmon", "rank", "step", "counters", "events",\n'
    "})\n"
    "VIEW_FIELDS = frozenset({\n"
    '    "ranks", "stale", "skew", "straggler",\n'
    "})\n"
)


class TestAggSchema:
    """The live-plane sibling of trace-vocab: aggregator.field() names
    and make_snapshot() keywords must be literals from the declared
    SNAPSHOT_FIELDS/VIEW_FIELDS schema — a typo'd field silently empties
    a kftop column instead of erroring."""

    def _tree(self, tmp_path):
        return _tmp_tree(tmp_path, {
            "kungfu_tpu/monitor/aggregator.py": MINI_AGGREGATOR,
            "kungfu_tpu/mod.py": "aggschema_bad.py",
        })

    def test_fixture_violations_caught(self, tmp_path):
        got = sorted((v.line, v.message)
                     for v in aggschema.check(self._tree(tmp_path)))
        assert [line for line, _ in got] == [13, 17, 21, 29, 33, 57], got
        assert "'stragler'" in got[0][1]
        assert "must be a string literal" in got[1][1]
        assert "without a field name" in got[2][1]
        assert "'stepp'" in got[3][1]
        assert "**dynamic" in got[4][1]
        # a VIEW-only field in make_snapshot raises at runtime, so lint
        # must flag it too (the union is only valid for field() reads)
        assert "'stale'" in got[5][1]

    def test_suppression_honored(self, tmp_path):
        flagged = {v.line for v in aggschema.check(self._tree(tmp_path))}
        assert 37 not in flagged, flagged  # the waived dynamic read

    def test_unrelated_receivers_not_flagged(self, tmp_path):
        flagged = {v.line for v in aggschema.check(self._tree(tmp_path))}
        assert 51 not in flagged and 52 not in flagged, flagged

    def test_schema_parsed_from_real_tree(self):
        from kungfu_tpu.analysis.aggschema import _schemas
        from kungfu_tpu.monitor.aggregator import SNAPSHOT_FIELDS, VIEW_FIELDS

        got = _schemas(ROOT)
        assert got["SNAPSHOT_FIELDS"] == set(SNAPSHOT_FIELDS)
        assert got["VIEW_FIELDS"] == set(VIEW_FIELDS)

    def test_kftop_is_covered_and_clean(self):
        # the viewer is the rule's main client: in scan scope, no findings
        assert os.path.isfile(
            os.path.join(ROOT, "kungfu_tpu", "monitor", "kftop.py"))
        assert [v for v in aggschema.check(ROOT)
                if "kftop" in v.path] == []

    def test_no_aggregator_module_is_silent(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "aggschema_bad.py"})
        assert aggschema.check(root) == []


MINI_LEDGER = (
    "LEDGER_FIELDS = frozenset({\n"
    '    "kfledger", "actor", "knob", "old", "new",\n'
    '    "evidence", "verdict", "effect_series",\n'
    "})\n"
)


class TestLedgerSchema:
    """The decision-ledger sibling of agg-schema: ledger.lfield() names
    and ledger_record()/record_decision() keywords must be literals from
    the declared LEDGER_FIELDS schema — a typo'd field silently drops a
    decision's evidence from the offline replay instead of erroring."""

    def _tree(self, tmp_path):
        return _tmp_tree(tmp_path, {
            "kungfu_tpu/monitor/ledger.py": MINI_LEDGER,
            "kungfu_tpu/mod.py": "ledgerschema_bad.py",
        })

    def test_fixture_violations_caught(self, tmp_path):
        got = sorted((v.line, v.message)
                     for v in ledgerschema.check(self._tree(tmp_path)))
        assert [line for line, _ in got] == [13, 17, 21, 29, 33, 41], got
        assert "'actr'" in got[0][1]
        assert "must be a string literal" in got[1][1]
        assert "without a field name" in got[2][1]
        assert "'knbo'" in got[3][1]
        assert "**dynamic" in got[4][1]
        assert "'evidnce'" in got[5][1]

    def test_suppression_honored(self, tmp_path):
        flagged = {v.line
                   for v in ledgerschema.check(self._tree(tmp_path))}
        assert 45 not in flagged, flagged  # the waived dynamic read

    def test_unrelated_receivers_not_flagged(self, tmp_path):
        flagged = {v.line
                   for v in ledgerschema.check(self._tree(tmp_path))}
        assert 57 not in flagged and 58 not in flagged, flagged

    def test_schema_mutation_is_caught(self, tmp_path):
        # mutation check: drop "verdict" from the declared schema and the
        # previously-clean read at line 9 must surface — proving the rule
        # reads the live declaration rather than a hardcoded field list
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/monitor/ledger.py":
                MINI_LEDGER.replace('"verdict", ', ""),
            "kungfu_tpu/mod.py": "ledgerschema_bad.py",
        })
        flagged = {v.line for v in ledgerschema.check(root)}
        assert 9 in flagged, flagged

    def test_schema_parsed_from_real_tree(self):
        from kungfu_tpu.analysis.ledgerschema import _schema
        from kungfu_tpu.monitor.ledger import LEDGER_FIELDS

        assert _schema(ROOT) == set(LEDGER_FIELDS)

    def test_actors_are_covered_and_clean(self):
        # every adaptive actor writes through record_decision: in scan
        # scope, no findings anywhere in the real tree
        assert ledgerschema.check(ROOT) == []

    def test_no_ledger_module_is_silent(self, tmp_path):
        root = _tmp_tree(tmp_path,
                         {"kungfu_tpu/mod.py": "ledgerschema_bad.py"})
        assert ledgerschema.check(root) == []


class TestBaselineAndJson:
    """kflint --json / --baseline: new rules can land with a suppression
    baseline instead of blocking on legacy findings."""

    def _seeded_root(self, tmp_path):
        return _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "collective_bad.py"})

    def test_json_output(self, tmp_path, capsys):
        root = self._seeded_root(tmp_path)
        rc = cli_main(["--root", root, "--checker", "collective-consistency",
                       "--json"])
        assert rc == 1
        findings = json.loads(capsys.readouterr().out)
        assert len(findings) == 4
        assert {f["checker"] for f in findings} == {"collective-consistency"}
        assert all({"path", "line", "message"} <= set(f) for f in findings)

    def test_baseline_roundtrip(self, tmp_path, capsys):
        root = self._seeded_root(tmp_path)
        baseline = str(tmp_path / "baseline.json")
        # snapshot the legacy findings ...
        rc = cli_main(["--root", root, "--checker", "collective-consistency",
                       "--write-baseline", baseline])
        assert rc == 0
        entries = json.load(open(baseline))
        assert len(entries) == 4
        # ... and the gate passes against them, but fails without them
        assert cli_main(["--root", root, "--checker",
                         "collective-consistency",
                         "--baseline", baseline]) == 0
        assert cli_main(["--root", root, "--checker",
                         "collective-consistency"]) == 1
        capsys.readouterr()

    def test_baseline_does_not_mask_new_findings(self, tmp_path, capsys):
        root = self._seeded_root(tmp_path)
        baseline = str(tmp_path / "baseline.json")
        cli_main(["--root", root, "--checker", "collective-consistency",
                  "--write-baseline", baseline])
        # drop one entry: that finding is now "new" again
        entries = json.load(open(baseline))
        json.dump(entries[:-1], open(baseline, "w"))
        assert cli_main(["--root", root, "--checker",
                         "collective-consistency",
                         "--baseline", baseline]) == 1
        capsys.readouterr()

    def test_malformed_baseline_is_loud(self, tmp_path, capsys):
        root = self._seeded_root(tmp_path)
        bad = tmp_path / "bad.json"
        bad.write_text('{"not": "a list"}')
        assert cli_main(["--root", root, "--baseline", str(bad)]) == 2
        capsys.readouterr()


class TestEnvContract:
    def test_unregistered_read_and_suppression(self, tmp_path):
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/utils/envs.py": MINI_REGISTRY,
            "kungfu_tpu/mod.py": "env_bad.py",
        })
        got = envcheck.check(root)
        assert len(got) == 1, [v.render() for v in got]
        assert "KF_TOTALLY_UNREGISTERED_KNOB" in got[0].message

    def test_dead_registry_entry(self, tmp_path):
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/utils/envs.py":
                MINI_REGISTRY.replace(
                    "=================  ===========================\n\"\"\"",
                    "``KF_NEVER_READ``  orphaned entry\n"
                    "=================  ===========================\n\"\"\"",
                ),
            "kungfu_tpu/mod.py":
                "import os\nx = os.environ.get('KF_SELF_SPEC')\n",
        })
        got = envcheck.check(root)
        assert len(got) == 1, [v.render() for v in got]
        assert "KF_NEVER_READ" in got[0].message
        assert "nothing in the tree reads it" in got[0].message

    def test_seeding_a_real_module_fails_the_gate(self, tmp_path):
        """Acceptance: a drifted KF_* read in a real module flips the
        suite red (simulated on a copied slice of the real tree)."""
        real = open(os.path.join(ROOT, "kungfu_tpu", "utils", "trace.py")).read()
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/utils/envs.py":
                open(os.path.join(ROOT, "kungfu_tpu", "utils", "envs.py")).read(),
            "kungfu_tpu/utils/trace.py":
                real + "\n_drift = __import__('os').environ.get('KF_SEEDED_DRIFT')\n",
        })
        got = envcheck.check(root)
        assert any("KF_SEEDED_DRIFT" in v.message for v in got), \
            [v.render() for v in got]


def _shard_check_all(root):
    return (shardaxis.check(root) + shardspec.check(root)
            + recompilehazard.check(root))


class TestShardAxis:
    """The kf-shard axis rule: literal collective axes must be declared
    by SOME mesh (vocabulary layer — the one-token-typo backbone) and
    bound in EVERY statically-known calling context (environment
    layer)."""

    def test_fixture_violations_caught(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "shard_axis_bad.py"})
        got = sorted((v.line, v.message) for v in shardaxis.check(root))
        assert [line for line, _ in got] == [16, 28, 44], got
        assert "no Mesh/pmap in the tree declares" in got[0][1]
        # the env-layer finding names the live environment AND the entry
        assert "not bound in the axis environment {x}" in got[1][1]
        assert "shard_map at" in got[1][1]
        assert "default axis 'zz'" in got[2][1]

    def test_suppression_honored(self, tmp_path):
        # the waived psum("q") on the allow() line must not surface
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "shard_axis_bad.py"})
        assert all(v.line != 19 for v in shardaxis.check(root))

    def test_good_fixture_clean(self, tmp_path):
        """partial(shard_map, mesh=...), nested sub-mesh, two-mesh
        helper with parameter axes, P(None, 'x') — all compliant idioms
        must pass all three kf-shard rules untouched."""
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "shard_good.py"})
        assert _shard_check_all(root) == [], \
            [v.render() for v in _shard_check_all(root)]

    def test_two_mesh_helper_no_cross_contamination(self, tmp_path):
        """A helper with a LITERAL axis reached from two meshes with
        different axis sets: valid under mesh A, a hang under mesh B —
        the union of the two environments must NOT mask it."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\n"
                "import numpy as np\n"
                "from jax.experimental.shard_map import shard_map\n"
                "from jax.sharding import Mesh, PartitionSpec as P\n\n\n"
                "def helper(a):\n"
                "    return jax.lax.psum(a, 'x')\n\n\n"
                "def build():\n"
                "    mx = Mesh(np.array(jax.devices()), ('x',))\n"
                "    my = Mesh(np.array(jax.devices()), ('y',))\n\n"
                "    def bx(a):\n"
                "        return helper(a)\n\n"
                "    def by(a):\n"
                "        return helper(a)\n\n"
                "    fx = shard_map(bx, mesh=mx, in_specs=(P('x'),),\n"
                "                   out_specs=P())\n"
                "    fy = shard_map(by, mesh=my, in_specs=(P(None, 'y'),),\n"
                "                   out_specs=P())\n"
                "    return fx, fy\n",
        })
        got = shardaxis.check(root)
        assert len(got) == 1, [v.render() for v in got]
        assert got[0].line == 8
        assert "not bound in the axis environment {y}" in got[0].message

    def test_pmap_axis_name_binds_environment(self, tmp_path):
        """pmap(f, axis_name=...) declares the axis and binds it in the
        mapped body; other declared axes are still unbound there."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\n"
                "import numpy as np\n"
                "from jax.sharding import Mesh\n\n"
                "MESH = Mesh(np.array(jax.devices()), ('x',))\n\n\n"
                "def body(g):\n"
                "    ok = jax.lax.psum(g, 'batch')\n"
                "    return ok + jax.lax.psum(g, 'x')\n\n\n"
                "def build():\n"
                "    return jax.pmap(body, axis_name='batch')\n",
        })
        got = shardaxis.check(root)
        assert len(got) == 1, [v.render() for v in got]
        assert got[0].line == 10
        assert "'x'" in got[0].message
        assert "not bound in the axis environment {batch}" in got[0].message

    def test_vocabulary_from_constant_table(self, tmp_path):
        """Axis constants resolve through module-level tables and
        imports, the way parallel/mesh.py declares them."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/meshmod.py":
                "import jax\nimport numpy as np\n"
                "from jax.sharding import Mesh\n\n"
                "AXIS_A = 'a'\nAXES = (AXIS_A, 'b')\n\n\n"
                "def build():\n"
                "    return Mesh(np.array(jax.devices()), AXES)\n",
            "kungfu_tpu/user.py":
                "import jax\n"
                "from kungfu_tpu.meshmod import AXIS_A\n\n\n"
                "def ok(g):\n"
                "    return jax.lax.psum(g, AXIS_A)\n\n\n"
                "def bad(g):\n"
                "    return jax.lax.psum(g, 'c')\n",
        })
        got = shardaxis.check(root)
        assert len(got) == 1, [v.render() for v in got]
        assert got[0].path.endswith("user.py") and "'c'" in got[0].message


class TestShardSpec:
    """PartitionSpec validity: axis-vs-mesh, duplicates, and
    in_specs/out_specs arity against the mapped function."""

    def test_fixture_violations_caught(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "shard_spec_bad.py"})
        got = sorted((v.line, v.message) for v in shardspec.check(root))
        assert [line for line, _ in got] == [18, 21, 23, 30, 33, 40], got
        assert "declares only {x, y}" in got[0][1]          # in_specs axis
        assert "twice" in got[1][1]                          # duplicate
        assert "takes 2 positional parameter(s)" in got[2][1]  # in arity
        assert "returns a 2-tuple" in got[3][1]              # out arity
        assert "NamedSharding" in got[4][1]                  # NamedSharding
        assert "no Mesh/pmap in the tree declares" in got[5][1]  # vocab

    def test_suppression_honored(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "shard_spec_bad.py"})
        # the waived P("qq") (allow line) must not surface
        assert all("qq" not in v.message for v in shardspec.check(root))

    def test_unconstrained_dims_clean(self, tmp_path):
        """PartitionSpec(None, 'x') — None is an unconstrained dim."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\nimport numpy as np\n"
                "from jax.sharding import Mesh, PartitionSpec as P\n\n\n"
                "def build():\n"
                "    mesh = Mesh(np.array(jax.devices()), ('x',))\n"
                "    return P(None, 'x'), P(), P(('x',), None)\n",
        })
        assert shardspec.check(root) == [], \
            [v.render() for v in shardspec.check(root)]


class TestRecompileHazard:
    """Resize-safety: membership constants, static-arg hazards, and
    world-size closure leaks in compiled code."""

    def test_fixture_violations_caught(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "recompile_bad.py"})
        got = sorted((v.line, v.message)
                     for v in recompilehazard.check(root))
        assert [line for line, _ in got] == [10, 11, 12, 22, 31, 32, 33], got
        assert "device_count()" in got[0][1]
        assert "len(peers)" in got[1][1]
        assert "environment read" in got[2][1]
        assert "closes over `world`" in got[3][1]
        assert "per-step-varying" in got[4][1]
        assert "out of range" in got[5][1]
        assert "static_argnames" in got[6][1]

    def test_suppression_honored(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "recompile_bad.py"})
        assert all(v.line != 13 for v in recompilehazard.check(root))

    def test_epoch_scoped_comm_not_flagged(self, tmp_path):
        """comm.size closed into a per-epoch step builder is the
        SANCTIONED pattern (zero.py) — it must stay clean."""
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "recompile_bad.py"})
        flagged = {v.line for v in recompilehazard.check(root)}
        assert not any(line >= 37 for line in flagged), flagged

    def test_mesh_closure_not_flagged(self, tmp_path):
        """Closing over a Mesh built from jax.devices() is THE shard_map
        pattern — the mesh is rebuilt per epoch by construction."""
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "shard_good.py"})
        assert recompilehazard.check(root) == [], \
            [v.render() for v in recompilehazard.check(root)]


class TestShardMutationProof:
    """The acceptance criterion: a one-token axis-name flip in
    parallel/tp.py (or train.py) and a one-axis PartitionSpec flip in
    parallel/zero.py must flip kflint red; the unmutated files pass all
    three rules with no baseline."""

    _FILES = ("mesh.py", "tp.py", "zero.py", "train.py", "ring.py",
              "moe.py")

    def _tree(self, tmp_path, mutate=None):
        files = {}
        for fn in self._FILES:
            src = open(os.path.join(
                ROOT, "kungfu_tpu", "parallel", fn)).read()
            if mutate and fn in mutate:
                mutated = mutate[fn](src)
                assert mutated != src, f"mutation must change {fn}"
                src = mutated
            files[f"kungfu_tpu/parallel/{fn}"] = src
        return _tmp_tree(tmp_path, files)

    def test_unmutated_parallel_clean(self, tmp_path):
        root = self._tree(tmp_path)
        assert _shard_check_all(root) == [], \
            [v.render() for v in _shard_check_all(root)]

    def test_tp_axis_token_flip_caught(self, tmp_path):
        root = self._tree(tmp_path, mutate={
            "tp.py": lambda s: s.replace(
                "jax.lax.psum(g, axis)", 'jax.lax.psum(g, "tq")'),
        })
        got = [v for v in shardaxis.check(root)
               if v.path.endswith("tp.py")]
        assert got and "'tq'" in got[0].message, \
            [v.render() for v in shardaxis.check(root)]

    def test_train_axis_token_flip_caught(self, tmp_path):
        # flipping the ppermute's pipeline axis to a typo'd token
        root = self._tree(tmp_path, mutate={
            "train.py": lambda s: s.replace(
                "jax.lax.ppermute(out, AXIS_PP, perm)",
                'jax.lax.ppermute(out, "ppx", perm)'),
        })
        got = [v for v in shardaxis.check(root)
               if v.path.endswith("train.py")]
        assert got and "'ppx'" in got[0].message

    def test_zero_partition_spec_flip_caught(self, tmp_path):
        root = self._tree(tmp_path, mutate={
            "zero.py": lambda s: s.replace(
                "lambda s: P(axes) if s.ndim else P(), state_shapes",
                "lambda s: P('dq') if s.ndim else P(), state_shapes"),
        })
        got = [v for v in shardspec.check(root)
               if v.path.endswith("zero.py")]
        assert got and "'dq'" in got[0].message

    def test_mutations_fail_the_cli(self, tmp_path, capsys):
        """The same flip through the kflint CLI (what check.sh runs)."""
        root = self._tree(tmp_path, mutate={
            "tp.py": lambda s: s.replace(
                "jax.lax.psum(g, axis)", 'jax.lax.psum(g, "tq")'),
        })
        args = ["--root", root]
        for c in SHARD_CHECKERS:
            args += ["--checker", c]
        assert cli_main(args) == 1
        capsys.readouterr()


class TestJitSyncInterprocedural:
    """The migrated jit-sync: host syncs are found at ANY call depth
    from the jitted root, not one module-local level."""

    def test_depth_two_sync_caught(self, tmp_path):
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "jit_sync_deep.py"})
        got = jitpurity.check(root)
        assert len(got) == 1, [v.render() for v in got]
        assert got[0].line == 18
        assert "in jit scope `level2`" in got[0].message
        assert "called from jitted step" in got[0].message

    def test_static_shape_locals_stay_legal(self, tmp_path):
        """int() over shape-derived locals (moe.py's capacity math) is
        trace-static and must not be flagged at interprocedural depth."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\n\n\n"
                "@jax.jit\n"
                "def step(x):\n"
                "    return helper(x)\n\n\n"
                "def helper(x):\n"
                "    t = x.shape[0]\n"
                "    cap = int(max(1, t * 2))\n"
                "    bad = int(x)\n"
                "    return cap + bad\n",
        })
        got = jitpurity.check(root)
        assert [v.line for v in got] == [12], [v.render() for v in got]


class TestSingleParse:
    """The kflint perf satellite: one full run parses each file exactly
    once — the module cache in analysis/core.py is shared by all
    eighteen rules AND the call graph AND the kf-det taint engine."""

    def test_each_file_parsed_once_per_run(self, tmp_path):
        from kungfu_tpu.analysis import core

        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/utils/envs.py": MINI_REGISTRY,
            "kungfu_tpu/mod.py": "collective_bad.py",
            "kungfu_tpu/mod2.py": "shard_axis_bad.py",
            "kungfu_tpu/mod3.py": "env_bad.py",
        })
        core.clear_parse_cache()
        run_checkers(root)
        counts = {p: c for p, c in core.PARSE_COUNTS.items()
                  if p.startswith(str(tmp_path))}
        assert len(counts) == 4, counts
        assert all(c == 1 for c in counts.values()), counts

    def test_full_tree_single_parse(self, tree_run):
        """On the REAL tree — every checker plus the taint engine plus
        the call graph plus the axis env still cost one parse per file
        (the <10s full-run budget depends on this)."""
        _, parse_counts = tree_run
        counts = {p: c for p, c in parse_counts.items()
                  if p.startswith(os.path.join(ROOT, "kungfu_tpu"))}
        over = {p: c for p, c in counts.items() if c != 1}
        assert counts and not over, over

    def test_cache_invalidates_on_rewrite(self, tmp_path):
        """Rewriting a file between runs re-parses it (stat-keyed cache,
        so fixture tests that mutate trees stay correct)."""
        import time

        from kungfu_tpu.analysis import core

        mod = tmp_path / "kungfu_tpu" / "mod.py"
        _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "env_bad.py"})
        core.clear_parse_cache()
        first = core.parse_module(str(mod))
        mod.write_text("x = 1\n")
        second = core.parse_module(str(mod))
        assert first.source != second.source
        assert core.PARSE_COUNTS[str(mod)] == 2


class TestReviewRegressions:
    """Pins for the code-review findings on the kf-shard landing."""

    def test_bound_method_shard_map_arity_clean(self, tmp_path):
        """shard_map(self._body, ...) diffs in_specs against the CALLED
        arity — `self` must not count as a missing spec entry."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\nimport numpy as np\n"
                "from jax.experimental.shard_map import shard_map\n"
                "from jax.sharding import Mesh, PartitionSpec as P\n\n\n"
                "class Owner:\n"
                "    def __init__(self):\n"
                "        self.mesh = Mesh(np.array(jax.devices()), ('x',))\n\n"
                "    def _body(self, a):\n"
                "        return a\n\n"
                "    def build(self):\n"
                "        return shard_map(self._body, mesh=self.mesh,\n"
                "                         in_specs=(P('x'),),\n"
                "                         out_specs=P('x'))\n",
        })
        assert shardspec.check(root) == [], \
            [v.render() for v in shardspec.check(root)]

    def test_all_gather_dim_kwarg_does_not_shadow_axis(self, tmp_path):
        """lax.all_gather(g, 'typo', axis=0): the int DIMENSION kwarg
        must not shadow the positional axis-NAME typo."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\nimport numpy as np\n"
                "from jax.sharding import Mesh\n\n"
                "MESH = Mesh(np.array(jax.devices()), ('x',))\n\n\n"
                "def f(g):\n"
                "    return jax.lax.all_gather(g, 'tq', axis=0, tiled=True)\n",
        })
        got = shardaxis.check(root)
        assert len(got) == 1 and "'tq'" in got[0].message, \
            [v.render() for v in got]

    def test_traced_prod_get_still_syncs(self, tmp_path):
        """float(x.prod()) / state.get() on traced values are host
        syncs; int(os.environ.get(...)) is trace-static config."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import os\n\nimport jax\n\n\n"
                "@jax.jit\n"
                "def step(x):\n"
                "    bad = float(x.prod())\n"
                "    ok = int(os.environ.get('KF_K', '4'))\n"
                "    return bad + ok\n",
        })
        got = jitpurity.check(root)
        assert [v.line for v in got] == [8], [v.render() for v in got]

    def test_clear_parse_cache_cascades_to_derived_caches(self, tmp_path):
        """Rewriting a file in the SAME root + clear_parse_cache() must
        re-derive the call graph and axis environment — stale caches
        would silently return the pre-rewrite findings."""
        from kungfu_tpu.analysis import core

        src_ok = (
            "import jax\nimport numpy as np\n"
            "from jax.sharding import Mesh\n\n"
            "MESH = Mesh(np.array(jax.devices()), ('x',))\n\n\n"
            "def f(g):\n"
            "    return jax.lax.psum(g, 'x')\n"
        )
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": src_ok})
        assert shardaxis.check(root) == []
        (tmp_path / "kungfu_tpu" / "mod.py").write_text(
            src_ok.replace("psum(g, 'x')", "psum(g, 'typo')"))
        core.clear_parse_cache()
        got = shardaxis.check(root)
        assert len(got) == 1 and "'typo'" in got[0].message, \
            [v.render() for v in got]

    def test_syntax_error_file_fails_the_suite(self, tmp_path):
        """An unparseable module is invisible to every rule — jit-sync
        owns surfacing it so the suite can't go green unanalyzed."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py": "def broken(:\n    pass\n",
        })
        got = jitpurity.check(root)
        assert len(got) == 1, [v.render() for v in got]
        assert "syntax error prevents analysis" in got[0].message

    def test_module_level_jit_wrapping_in_scope(self, tmp_path):
        """`train_step = jax.jit(step)` at module level enters jit
        scope — the pre-callgraph checker saw these; the axisenv map
        must too."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\n\n\n"
                "def step(x):\n"
                "    return x.item()\n\n\n"
                "train_step = jax.jit(step)\n",
        })
        got = jitpurity.check(root)
        assert len(got) == 1 and got[0].line == 5, \
            [v.render() for v in got]

    def test_np_prod_on_traced_value_still_syncs(self, tmp_path):
        """float(np.prod(x)) concretizes a tracer — flagged; shape-fed
        np.prod stays trace-static."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\nimport numpy as np\n\n\n"
                "@jax.jit\n"
                "def step(x):\n"
                "    bad = float(np.prod(x))\n"
                "    ok = int(np.prod(x.shape))\n"
                "    return bad + ok\n",
        })
        got = jitpurity.check(root)
        assert [v.line for v in got] == [7], [v.render() for v in got]

    def test_kwonly_static_argnames_clean(self, tmp_path):
        """Keyword-only params are legal static_argnames targets."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\n\n\n"
                "def f(x, *, donate):\n"
                "    return x if donate else -x\n\n\n"
                "g = jax.jit(f, static_argnames='donate')\n",
        })
        assert recompilehazard.check(root) == [], \
            [v.render() for v in recompilehazard.check(root)]

    def test_restricted_dirs_exclude_scan_files(self, tmp_path):
        """iter_py_files(dirs=('kungfu_tpu',)) must not widen to the
        top-level scan files a deliberately-scoped rule excluded."""
        from kungfu_tpu.analysis.core import iter_py_files

        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py": "x = 1\n",
            "__graft_entry__.py": "y = 2\n",
        })
        default = {os.path.basename(p) for p in iter_py_files(root)}
        narrowed = {os.path.basename(p)
                    for p in iter_py_files(root, dirs=("kungfu_tpu",))}
        assert "__graft_entry__.py" in default
        assert "__graft_entry__.py" not in narrowed

    def test_nested_binding_definition_order_independent(self, tmp_path):
        """The inner-mesh body defined BEFORE the function that maps the
        outer body: the fixpoint must not freeze a stale inner-only
        context (definition-order-dependent false positive)."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\nimport numpy as np\n"
                "from jax.experimental.shard_map import shard_map\n"
                "from jax.sharding import Mesh, PartitionSpec as P\n\n"
                "INNER = Mesh(np.array(jax.devices()[:2]), ('y',))\n"
                "OUTER = Mesh(np.array(jax.devices()), ('x',))\n\n\n"
                "def outer_body(a):\n"
                "    def inner_body(b):\n"
                "        s = jax.lax.psum(b, 'y')\n"
                "        return jax.lax.psum(s, 'x')\n\n"
                "    return shard_map(inner_body, mesh=INNER,\n"
                "                     in_specs=(P('y'),),\n"
                "                     out_specs=P('y'))(a)\n\n\n"
                "def make():\n"
                "    return shard_map(outer_body, mesh=OUTER,\n"
                "                     in_specs=(P('x'),),\n"
                "                     out_specs=P('x'))\n",
        })
        assert shardaxis.check(root) == [], \
            [v.render() for v in shardaxis.check(root)]

    def test_lax_axis_size_is_trace_static(self, tmp_path):
        """int(lax.axis_size(...)) is the suite's own prescribed remedy
        for membership constants — jit-sync must not flag it."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\nimport numpy as np\n"
                "from jax import lax\n"
                "from jax.sharding import Mesh\n\n"
                "MESH = Mesh(np.array(jax.devices()), ('dp',))\n\n\n"
                "@jax.jit\n"
                "def step(x):\n"
                "    n = int(lax.axis_size('dp'))\n"
                "    return x / n\n",
        })
        assert jitpurity.check(root) == [], \
            [v.render() for v in jitpurity.check(root)]

    def test_bound_method_jit_wrapping_in_scope(self, tmp_path):
        """`train = jax.jit(t.step)` marks the same-module method as
        traced (the pre-callgraph over-report stance for jit SCOPE)."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\n\n\n"
                "class Trainer:\n"
                "    def step(self, x):\n"
                "        return x.item()\n\n\n"
                "t = Trainer()\n"
                "train = jax.jit(t.step)\n",
        })
        got = jitpurity.check(root)
        assert len(got) == 1 and got[0].line == 6, \
            [v.render() for v in got]

    def test_decorator_pmap_declares_and_binds_axis(self, tmp_path):
        """@partial(jax.pmap, axis_name='batch') declares the axis AND
        binds it in the decorated body; other axes stay unbound."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "from functools import partial\n\n"
                "import jax\n\n\n"
                "@partial(jax.pmap, axis_name='batch')\n"
                "def ok(g):\n"
                "    return jax.lax.psum(g, 'batch')\n\n\n"
                "@partial(jax.pmap, axis_name='batch')\n"
                "def bad(g):\n"
                "    return jax.lax.psum(g, 'other')\n",
        })
        got = shardaxis.check(root)
        assert len(got) == 1 and got[0].line == 13, \
            [v.render() for v in got]
        assert "'other'" in got[0].message

    def test_import_resolution_needs_dotted_boundary(self, tmp_path):
        """`from core import f` (out-of-tree) must not suffix-match an
        unrelated in-tree module and mark its `f` as jitted."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/score.py":
                "def f(x):\n"
                "    return x.item()\n",
            "kungfu_tpu/user.py":
                "import jax\n"
                "from core import f\n\n"
                "g = jax.jit(f)\n",
        })
        assert jitpurity.check(root) == [], \
            [v.render() for v in jitpurity.check(root)]

    def test_repeated_constant_references_resolve(self, tmp_path):
        """AXES = (A, B) with A and B aliasing the same constant must
        still evaluate (the cycle guard is a stack, not a visited set)."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\nimport numpy as np\n"
                "from jax.sharding import Mesh\n\n"
                "AXIS_DP = 'dp'\n"
                "A = AXIS_DP\n"
                "B = AXIS_DP\n"
                "AXES = (A, B)\n"
                "MESH = Mesh(np.array(jax.devices()), AXES)\n\n\n"
                "def f(g):\n"
                "    return jax.lax.psum(g, 'dp')\n",
        })
        assert shardaxis.check(root) == [], \
            [v.render() for v in shardaxis.check(root)]

    def test_static_local_chain_in_reverse_order(self, tmp_path):
        """A 4-link shape-derived chain assigned in reverse textual
        order is still trace-static (closure runs to convergence)."""
        root = _tmp_tree(tmp_path, {
            "kungfu_tpu/mod.py":
                "import jax\n\n\n"
                "@jax.jit\n"
                "def step(x):\n"
                "    for _ in range(2):\n"
                "        d = c * 2\n"
                "        c = b * 2\n"
                "        b = a * 2\n"
                "        a = x.shape[0]\n"
                "    return int(d) + x\n",
        })
        assert jitpurity.check(root) == [], \
            [v.render() for v in jitpurity.check(root)]

    def test_parse_cache_one_entry_per_path(self, tmp_path):
        """A rewritten file REPLACES its cache entry (no unbounded
        accumulation of historical parses)."""
        import time

        from kungfu_tpu.analysis import core

        mod = tmp_path / "kungfu_tpu" / "mod.py"
        _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": "x = 1\n"})
        core.clear_parse_cache()
        core.parse_module(str(mod))
        for i in range(5):
            mod.write_text(f"x = {i} + 100\n" * (i + 1))
            core.parse_module(str(mod))
        entries = [k for k in core._MODULE_CACHE if k == str(mod)]
        assert len(entries) == 1, core._MODULE_CACHE.keys()


class TestProtoVerify:
    """The kf-verify SPMD protocol verifier (docs/lint.md).  Exact-line
    pins on the bad fixtures; geometry/mutation coverage lives in
    tests/test_protoverify.py."""

    def _check(self, tmp_path, fixture):
        from kungfu_tpu.analysis import callgraph, core
        root = _tmp_tree(tmp_path, {"kungfu_tpu/mod.py": fixture})
        core.clear_parse_cache()
        callgraph.invalidate_cache()
        return protoverify.check(root)

    def test_good_fixture_clean(self, tmp_path):
        got = self._check(tmp_path, "proto_good_mirror.py")
        assert got == [], [v.render() for v in got]

    def test_order_divergence_caught(self, tmp_path):
        """One-sided rank guard + both halves of the uniform bucket
        swap (reduce_scatter and all_gather tags run b{N-1-i})."""
        got = self._check(tmp_path, "proto_bad_order.py")
        assert {v.line for v in got} == {9, 15, 18}, \
            [v.render() for v in got]
        assert any("one side of a rank-dependent" in v.message
                   or "rank" in v.message for v in got if v.line == 9)
        assert all("canonical" in v.message
                   for v in got if v.line in (15, 18))

    def test_orphan_tags_caught(self, tmp_path):
        got = self._check(tmp_path, "proto_bad_orphan.py")
        assert {v.line for v in got} == {8, 11}, \
            [v.render() for v in got]

    def test_fence_cycle_caught(self, tmp_path):
        """Mirror arms that each post a recv, fence, then send — both
        ranks block inside the fence (2-rank simulation)."""
        got = self._check(tmp_path, "proto_bad_cycle.py")
        assert {v.line for v in got} == {8}, [v.render() for v in got]
        assert any("deadlock" in v.message for v in got)

    def test_proto_flag_registered(self):
        from kungfu_tpu.analysis.cli import CHECKERS, PROTO_CHECKERS
        assert PROTO_CHECKERS == (protoverify.CHECKER,)
        assert protoverify.CHECKER in CHECKERS
