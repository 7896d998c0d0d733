"""zoo-lint framework tests (the ``lint`` marker).

Three layers:

* fixture tests — one seeded violation per rule in a throwaway tree,
  asserting the finding lands with the right rule id, file and line
  (plus a negative twin and an allowlisted case);
* self-application — the real tree is lint-clean under the checked-in
  allowlist, the linter itself never imports jax, and the knob
  registry round-trips every ``ZOO_*`` name greppable in the tree;
* the in-suite strict gate — runs every AST pass over the repo and
  writes ``LINT.json`` at the root of the repo.

The compiled-HLO passes are fixture-tested here on synthetic module
text; their real-executable wiring lives in the compile-census tests
(test_llm_serving / test_spec_decode / the multichip smoke).
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from zoo_tpu.analysis import (
    Context,
    apply_allowlist,
    findings_json,
    load_allowlist,
    run_passes,
)

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(tmp_path, files):
    """Materialize ``{relpath: source}`` under tmp_path and return a
    Context rooted there (no allowlist unless the caller writes one)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Context(str(tmp_path),
                   allowlist_path=str(tmp_path / "zoo_lint_allow.txt"))


def _knob(name, **kw):
    from zoo_tpu.common.knobs import Knob
    kw.setdefault("type", "int")
    kw.setdefault("default", 1)
    kw.setdefault("help", "h")
    kw.setdefault("doc", "docs/x.md")
    return Knob(name=name, **kw)


# ---------------------------------------------------------------- knobs

class TestKnobPass:
    def test_undeclared_knob_caught_with_location(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": """\
                import os


                def f():  # zoo-lint: config-parse
                    return os.environ.get("ZOO_MYSTERY_KNOB")
            """,
            "docs/x.md": "ZOO_GOOD\n",
        })
        ctx.knob_registry = {}
        ctx.knob_table_docs = ()
        fs = run_passes(ctx, ["knobs"])
        hit = [f for f in fs if f.rule == "KNOB-UNDECLARED"]
        assert len(hit) == 1
        assert hit[0].file == "zoo_tpu/m.py" and hit[0].line == 5
        assert hit[0].detail == "ZOO_MYSTERY_KNOB"

    def test_registered_knob_is_clean_and_dead_knob_caught(self,
                                                           tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": """\
                import os


                def f():  # zoo-lint: config-parse
                    return os.environ.get("ZOO_GOOD")
            """,
            "zoo_tpu/common/__init__.py": "",
            "zoo_tpu/common/knobs.py": '_K = ("ZOO_GOOD", "ZOO_DEAD")\n',
            "docs/x.md": "ZOO_GOOD ZOO_DEAD\n",
        })
        ctx.knob_registry = {"ZOO_GOOD": _knob("ZOO_GOOD"),
                             "ZOO_DEAD": _knob("ZOO_DEAD")}
        ctx.knob_table_docs = ()
        fs = run_passes(ctx, ["knobs"])
        assert [f.detail for f in fs if f.rule == "KNOB-DEAD"] == \
            ["ZOO_DEAD"]
        assert not [f for f in fs
                    if f.rule == "KNOB-UNDECLARED"]

    def test_raw_env_read_outside_parse_site(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": """\
                import os


                def hot_path():
                    return os.environ.get("ZOO_GOOD")


                def blessed():  # zoo-lint: config-parse
                    return os.environ.get("ZOO_GOOD")
            """,
            "docs/x.md": "ZOO_GOOD\n",
        })
        ctx.knob_registry = {"ZOO_GOOD": _knob("ZOO_GOOD")}
        ctx.knob_table_docs = ()
        fs = [f for f in run_passes(ctx, ["knobs"])
              if f.rule == "KNOB-RAW-ENV"]
        assert len(fs) == 1
        assert (fs[0].file, fs[0].line) == ("zoo_tpu/m.py", 5)

    def test_raw_env_allowlisted(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": "import os\nV = os.environ.get('ZOO_GOOD')\n",
            "docs/x.md": "ZOO_GOOD\n",
            "zoo_lint_allow.txt":
                "KNOB-RAW-ENV zoo_tpu/m.py ZOO_GOOD  # fixture\n",
        })
        ctx.knob_registry = {"ZOO_GOOD": _knob("ZOO_GOOD")}
        ctx.knob_table_docs = ()
        fs = run_passes(ctx, ["knobs"])
        active, suppressed = apply_allowlist(
            fs, load_allowlist(ctx.allowlist_path))
        assert not [f for f in active if f.rule == "KNOB-RAW-ENV"]
        assert [f.rule for f in suppressed] == ["KNOB-RAW-ENV"]

    def test_undocumented_and_doc_drift(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": """\
                import os


                def f():  # zoo-lint: config-parse
                    return (os.environ.get("ZOO_GOOD"),
                            os.environ.get("ZOO_HIDDEN"))
            """,
            "docs/x.md": """\
                | Env | Default | Meaning |
                |---|---|---|
                <!-- zoo-knob-table:g begin -->
                | `ZOO_GOOD` | 999 | stale row |
                <!-- zoo-knob-table:g end -->
            """,
            "docs/y.md": "nothing here\n",
        })
        ctx.knob_registry = {
            "ZOO_GOOD": _knob("ZOO_GOOD", table="g"),
            "ZOO_HIDDEN": _knob("ZOO_HIDDEN", doc="docs/y.md"),
        }
        ctx.knob_table_docs = ("docs/x.md",)
        fs = run_passes(ctx, ["knobs"])
        assert [f.detail for f in fs
                if f.rule == "KNOB-UNDOCUMENTED"] == ["ZOO_HIDDEN"]
        drift = [f for f in fs if f.rule == "KNOB-DOC-DRIFT"]
        assert len(drift) == 1 and drift[0].file == "docs/x.md"
        assert drift[0].line == 3 and drift[0].detail == "g"

    def test_registry_value_alias_resolved(self, tmp_path):
        # the production call style: `from ... import value as
        # knob_value` — an unregistered name must NOT escape the lint
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": """\
                from zoo_tpu.common.knobs import value as knob_value

                X = knob_value("ZOO_NOT_REGISTERED")
            """,
            "docs/x.md": "x\n",
        })
        ctx.knob_registry = {}
        ctx.knob_table_docs = ()
        fs = [f for f in run_passes(ctx, ["knobs"])
              if f.rule == "KNOB-UNDECLARED"]
        assert len(fs) == 1 and fs[0].line == 3
        assert fs[0].detail == "ZOO_NOT_REGISTERED"

    def test_default_drift_caught(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": """\
                from zoo_tpu.util.resilience import env_int

                A = env_int("ZOO_GOOD", 1)    # matches the registry
                B = env_int("ZOO_GOOD", 99)   # drifted fallback
            """,
            "docs/x.md": "ZOO_GOOD\n",
        })
        ctx.knob_registry = {"ZOO_GOOD": _knob("ZOO_GOOD")}
        ctx.knob_table_docs = ()
        fs = [f for f in run_passes(ctx, ["knobs"])
              if f.rule == "KNOB-DEFAULT-DRIFT"]
        assert len(fs) == 1 and fs[0].line == 4
        assert "99" in fs[0].message and "1" in fs[0].message

    def test_env_constant_and_alias_resolution(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": """\
                import os

                MY_ENV = "ZOO_VIA_CONST"


                def f():
                    env = os.environ
                    return env.get(MY_ENV)
            """,
            "docs/x.md": "x\n",
        })
        ctx.knob_registry = {}
        ctx.knob_table_docs = ()
        fs = run_passes(ctx, ["knobs"])
        assert [f.detail for f in fs if f.rule == "KNOB-UNDECLARED"] \
            == ["ZOO_VIA_CONST"]
        assert [f.detail for f in fs if f.rule == "KNOB-RAW-ENV"] == \
            ["ZOO_VIA_CONST"]


# --------------------------------------------------------------- purity

class TestPurityPass:
    def test_jax_in_closure_caught(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/pure.py": """\
                # zoo-lint: jax-free
                from zoo_tpu import helper
            """,
            "zoo_tpu/helper.py": "import jax\n",
        })
        fs = run_passes(ctx, ["purity"])
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "PURITY-JAX"
        assert f.file == "zoo_tpu/pure.py" and f.line == 1
        assert "zoo_tpu/helper.py:1" in f.message

    def test_package_init_chain_counts(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/sub/__init__.py": "import jax.numpy\n",
            "zoo_tpu/sub/leaf.py": "X = 1\n",
            "zoo_tpu/pure.py": """\
                # zoo-lint: jax-free
                from zoo_tpu.sub.leaf import X
            """,
        })
        fs = run_passes(ctx, ["purity"])
        assert [f.rule for f in fs] == ["PURITY-JAX"]
        assert "zoo_tpu/sub/__init__.py" in fs[0].message

    def test_lazy_and_type_checking_imports_allowed(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/pure.py": """\
                # zoo-lint: jax-free
                from typing import TYPE_CHECKING

                if TYPE_CHECKING:
                    import jax


                def device_path():
                    import jax.numpy as jnp
                    return jnp
            """,
        })
        assert run_passes(ctx, ["purity"]) == []


# ---------------------------------------------------------------- locks

_LOCKED_CLASS = """\
    import threading


    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []  # guarded-by: _lock

        def %s
"""


class TestLockPass:
    def test_unguarded_access_caught(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": _LOCKED_CLASS % (
                "add(self, x):\n            self._items.append(x)\n"),
        })
        fs = run_passes(ctx, ["locks"])
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "LOCK-GUARD" and f.detail == "Box._items"
        assert f.file == "zoo_tpu/m.py" and f.line == 10

    def test_with_lock_and_escapes_clean(self, tmp_path):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": _LOCKED_CLASS % (
                "add(self, x):\n"
                "            with self._lock:\n"
                "                self._items.append(x)\n\n"
                "        def _drain_locked(self):\n"
                "            return list(self._items)\n\n"
                "        def peek(self):\n"
                "            return len(self._items)  "
                "# zoo-lint: holds-lock\n"),
        })
        assert run_passes(ctx, ["locks"]) == []


# ------------------------------------------------------------ telemetry

class TestTelemetryPass:
    def _ctx(self, tmp_path, body, metrics=None, events=None):
        ctx = _tree(tmp_path, {
            "zoo_tpu/__init__.py": "",
            "zoo_tpu/m.py": body,
        })
        ctx.metrics_catalog = metrics or {}
        ctx.event_catalog = frozenset(events or ())
        return ctx

    def test_undeclared_metric_and_event(self, tmp_path):
        ctx = self._ctx(tmp_path, """\
            from zoo_tpu.obs.metrics import counter
            from zoo_tpu.obs.flight import record_event

            C = counter("zoo_typo_total", "h", labels=("kind",))


            def f():
                record_event("unknown_kind")
        """)
        fs = run_passes(ctx, ["telemetry"])
        und = {f.detail: f for f in fs if f.rule == "TEL-UNDECLARED"}
        assert set(und) == {"zoo_typo_total", "event:unknown_kind"}
        assert und["zoo_typo_total"].line == 4

    def test_label_mismatch_and_dead_entry(self, tmp_path):
        ctx = self._ctx(
            tmp_path, """\
                from zoo_tpu.obs.metrics import gauge

                G = gauge("zoo_ok", "h", labels=("axis", "extra"))
            """,
            metrics={"zoo_ok": ("gauge", ("axis",)),
                     "zoo_never_created": ("counter", ())})
        fs = run_passes(ctx, ["telemetry"])
        assert [f.detail for f in fs if f.rule == "TEL-LABELS"] == \
            ["zoo_ok"]
        assert [f.detail for f in fs if f.rule == "TEL-DEAD"] == \
            ["zoo_never_created"]

    def test_aliased_ctor_and_matching_decl_clean(self, tmp_path):
        ctx = self._ctx(
            tmp_path, """\
                from zoo_tpu.obs.metrics import counter as _obs_counter

                C = _obs_counter("zoo_ok_total", "h", labels=("op",))
            """,
            metrics={"zoo_ok_total": ("counter", ("op",))})
        assert run_passes(ctx, ["telemetry"]) == []


# ------------------------------------------------------------------ hlo

_HLO_HEADER = (
    "HloModule jit_step, is_scheduled=true%s, "
    "entry_computation_layout={(%s)->(%s)}\n\n"
    "ENTRY %%main (p0: f32[4]) -> (s32[4,1]) {\n"
    "  ROOT %%t = (s32[4,1]{1,0}) tuple()\n}\n")


class TestHloPasses:
    def test_donation_dropped_caught(self):
        from zoo_tpu.analysis.hlo import (
            assert_donated,
            donation_findings,
        )
        good = _HLO_HEADER % (
            ", input_output_alias={ {0}: (1, {}, may-alias), "
            "{1}: (2, {}, may-alias) }",
            "f32[4]{0}, f32[8]{0}, f32[8]{0}", "f32[8]{0}, f32[8]{0}")
        assert donation_findings(good, 2, "fixture") == []
        bad = _HLO_HEADER % ("", "f32[4]{0}", "f32[4]{0}")
        fs = donation_findings(bad, 2, "fixture exec")
        assert len(fs) == 1 and fs[0].rule == "HLO-DONATION"
        assert fs[0].file == "fixture exec"
        assert "0 of 2" in fs[0].message
        with pytest.raises(AssertionError, match="donat"):
            assert_donated(bad, 2, "fixture exec")

    def test_host_transfer_logits_caught(self):
        from zoo_tpu.analysis.hlo import (
            assert_host_transfer,
            host_transfer_findings,
        )
        ok = _HLO_HEADER % ("", "f32[4]{0}",
                            "s32[4,1]{1,0}, f32[4,2,8]{2,1,0}")
        assert host_transfer_findings(ok, 4, 256) == []
        # slots x vocab logits in the entry outputs
        bad = _HLO_HEADER % ("", "f32[4]{0}",
                             "s32[4,1]{1,0}, f32[4,256]{1,0}")
        fs = host_transfer_findings(bad, 4, 256, label="decode exec")
        assert [f.rule for f in fs] == ["HLO-HOST-TRANSFER"]
        assert "vocab-sized" in fs[0].message
        # no token output at all
        none = _HLO_HEADER % ("", "f32[4]{0}", "f32[4,8]{1,0}")
        fs = host_transfer_findings(none, 4, 256)
        assert [f.detail for f in fs] == ["tokens"]
        with pytest.raises(AssertionError, match="vocab"):
            assert_host_transfer(bad, 4, 256)

    def test_sharding_plan_tp_params_caught(self):
        from zoo_tpu.analysis.hlo import (
            assert_plan_sharded,
            sharding_findings,
        )
        # megatron-sharded (64, 64) weight fed at FULL shape -> "TP
        # that isn't" on the entry parameters
        bad = _HLO_HEADER % ("", "f32[64,64]{1,0}, f32[4]{0}",
                             "s32[4,1]{1,0}")
        fs = sharding_findings(bad, [(64, 64)], [(4,)],
                               local_shapes=[(64, 32)],
                               check_params=True,
                               label="tp step")
        assert [f.rule for f in fs] == ["HLO-SHARDING"]
        assert "fed replicated" in fs[0].message
        good = _HLO_HEADER % ("", "f32[64,32]{1,0}, f32[4]{0}",
                              "s32[4,1]{1,0}")
        assert sharding_findings(good, [(64, 64)], [(4,)],
                                 local_shapes=[(64, 32)],
                                 check_params=True) == []
        with pytest.raises(AssertionError, match="TP that isn't"):
            assert_plan_sharded(bad, [(64, 64)], [(4,)],
                                local_shapes=[(64, 32)], plan="tp")

    def test_fsdp_output_rule_still_enforced(self):
        # the PR 8 rule through the generalized entry point: a
        # full-shape sharded tensor in the entry OUTPUTS
        from zoo_tpu.analysis.hlo import sharding_findings
        bad = _HLO_HEADER % ("", "f32[8,64]{1,0}",
                             "f32[64,64]{1,0}")
        fs = sharding_findings(bad, [(64, 64)],
                               local_shapes=[(8, 64)],
                               label="fsdp step")
        assert [f.rule for f in fs] == ["HLO-SHARDING"]
        assert "FSDP that isn't" in fs[0].message


# ------------------------------------------------- framework / allowlist

class TestFramework:
    def test_allowlist_requires_justification(self, tmp_path):
        from zoo_tpu.analysis import LintError
        p = tmp_path / "allow.txt"
        p.write_text("KNOB-DEAD zoo_tpu/m.py ZOO_X\n")
        with pytest.raises(LintError, match="justification"):
            load_allowlist(str(p))

    def test_stale_entries_reported_by_cli(self, tmp_path):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "zoo_lint.py"),
             "--allowlist", os.path.join(REPO, "zoo_lint_allow.txt"),
             "--strict"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr

    def test_findings_json_shape(self):
        from zoo_tpu.analysis import Finding
        doc = json.loads(findings_json(
            [Finding("R-1", "a.py", 3, "m", "h", "d")], [],
            {"git_rev": "x"}))
        assert doc["n_active"] == 1
        assert doc["active"][0]["rule"] == "R-1"
        assert doc["active_by_rule"] == {"R-1": 1}


# ------------------------------------------------- self-application gate

class TestSelfApplication:
    def test_linter_never_imports_jax(self):
        """The purity contract applies to the lint runner itself: a
        fresh interpreter that runs every AST pass over the real tree
        must finish without jax in sys.modules."""
        code = (
            "import sys\n"
            "import zoo_tpu.analysis as A\n"
            "fs = A.run_passes(A.Context(%r))\n"
            "assert 'jax' not in sys.modules, 'linter imported jax'\n"
            "print('PURE', len(fs))\n" % REPO)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=REPO,
                             timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        assert out.stdout.startswith("PURE"), out.stdout

    def test_knob_registry_roundtrips_greppable_names(self):
        """Every ZOO_* token greppable in the code tree resolves
        against the registry (exactly, or as a prefix of a registered
        family), and every registered knob is greppable somewhere —
        the registry and the tree can never drift apart silently."""
        from zoo_tpu.common.knobs import KNOBS
        tokens = set()
        roots = ["zoo_tpu", "scripts"]
        files = ["__graft_entry__.py"]
        for root in roots:
            for dirpath, dirnames, filenames in os.walk(
                    os.path.join(REPO, root)):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"]
                files.extend(os.path.join(dirpath, fn)
                             for fn in filenames
                             if fn.endswith(".py"))
        for path in files:
            if not os.path.isabs(path):
                path = os.path.join(REPO, path)
            if not os.path.exists(path):
                continue
            with open(path, encoding="utf-8", errors="replace") as f:
                # (?<!) excludes _ZOO_* private IPC vars — a leading
                # underscore is the "not a knob" convention
                tokens.update(re.findall(
                    r"(?<![A-Z0-9_])ZOO_[A-Z0-9_]+[A-Z0-9]", f.read()))
        assert tokens, "grep found nothing — wrong root?"
        unknown = {
            t for t in tokens
            if t not in KNOBS
            and not any(k.startswith(t) for k in KNOBS)}
        assert not unknown, (
            f"ZOO_* names in the tree but not in the registry: "
            f"{sorted(unknown)} — register them in "
            "zoo_tpu/common/knobs.py")
        src = "\n".join(open(p, encoding="utf-8",
                             errors="replace").read()
                        for p in files if os.path.exists(p)
                        and "common/knobs.py" not in p.replace(
                            os.sep, "/"))
        # f-string reads (`f"ZOO_MESH_{name}"`) keep a whole knob
        # family alive through their literal prefix
        prefixes = set(re.findall(r"(ZOO_[A-Z0-9_]+_)\{", src))
        dead = {k for k in KNOBS if k not in src
                and not any(k.startswith(p) for p in prefixes)}
        assert not dead, (
            f"registered knobs not greppable anywhere: {sorted(dead)}")

    def test_tree_is_lint_clean_and_emits_report(self):
        """The in-suite strict gate: every AST pass over the real
        tree, zero non-allowlisted findings, machine-readable report
        written beside the BENCH_*.json trajectory files."""
        ctx = Context(REPO)
        findings = run_passes(ctx)
        entries = load_allowlist(ctx.allowlist_path)
        active, suppressed = apply_allowlist(findings, entries)
        report = findings_json(active, suppressed,
                               {"source": "tests/test_zoo_lint.py"})
        with open(os.path.join(REPO, "LINT.json"), "w",
                  encoding="utf-8") as f:
            f.write(report)
        assert not active, "\n" + "\n".join(
            f.format() for f in active)
        stale = [e for e in entries if not e.used]
        assert not stale, f"stale allowlist entries: " \
            f"{[(e.rule, e.file, e.detail) for e in stale]}"

    def test_declared_jax_free_modules_cover_the_contract(self):
        """The modules the chaos smokes rely on importing without jax
        all carry the machine-readable marker (regression against the
        marker being dropped in a refactor)."""
        from zoo_tpu.analysis.purity import jax_free_modules
        declared = set(jax_free_modules(Context(REPO)))
        for must in (
                "zoo_tpu/orca/learn/guard.py",
                "zoo_tpu/serving/registry.py",
                "zoo_tpu/serving/llm/kv_cache.py",
                "zoo_tpu/serving/ejection.py",
                "zoo_tpu/serving/llm/synthetic.py",
                "zoo_tpu/util/manifest.py",
                "zoo_tpu/util/resilience.py",
                "zoo_tpu/common/knobs.py",
                "zoo_tpu/obs/catalog.py",
                "zoo_tpu/analysis/framework.py",
        ):
            assert must in declared, f"{must} lost its marker"
