"""graftlint (turboprune_tpu.analysis) tests.

Four layers, mirroring the subsystem's contract:

1. Per-rule fixtures: every rule has a BAD snippet it must catch and a
   GOOD twin it must stay silent on — the rule set's behavior is pinned
   code-first, so a rule change that widens/narrows matching fails here
   before it floods (or silently stops protecting) the repo.
2. Engine mechanics: waiver parsing/scoping/reasons, test-file rule
   relaxations, reporter shapes, CLI exit codes.
3. PROJECT-MODE fixtures (PR 3): every interprocedural upgrade has a
   catching/non-catching pair SPANNING MODULES (the per-file layer's
   documented blind spot), and every config rule has a yaml pair checked
   against a fixture schema; call-path traces and yaml waivers are pinned
   the same way.
4. The SELF-GATE: the analyzer runs over the whole package + conf + tests
   in both per-file and --project mode and asserts zero unwaived findings
   and zero stale waivers. This is the test that makes the rule set
   self-enforcing: any future PR that introduces a host sync N calls deep
   in a jitted region, a typo'd conf key, or a swallowed exception fails
   tier-1 until the code is fixed or the site carries a reasoned waiver.
"""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest

from turboprune_tpu.analysis import (
    CONF_RULES,
    RULES,
    analyze_files,
    analyze_paths,
    analyze_project,
    analyze_source,
    render_json,
    render_text,
)
from turboprune_tpu.analysis.cli import build_parser, main as cli_main

REPO = Path(__file__).resolve().parents[1]


def run(src: str, path="lib/snippet.py", select=None):
    """Unwaived findings for a dedented source snippet."""
    findings, _ = analyze_source(textwrap.dedent(src), path, select=select)
    return [f for f in findings if not f.waived]


def rules_hit(src: str, **kw):
    return {f.rule for f in run(src, **kw)}


# --------------------------------------------------------------- fixtures
# rule id -> (bad snippet that MUST trigger it, good twin that MUST NOT)
FIXTURES = {
    "jit-host-sync": (
        """
        import jax

        @jax.jit
        def step(state, batch):
            loss = (state - batch).sum()
            return loss.item()
        """,
        """
        import jax

        @jax.jit
        def step(state, batch):
            return (state - batch).sum()

        def epoch(state, batch):
            loss = step(state, batch)
            return loss.item()
        """,
    ),
    "retrace-hazard": (
        """
        import jax

        def train(steps, x):
            for _ in range(steps):
                x = jax.jit(lambda a: a + 1)(x)
            return x
        """,
        """
        import jax

        def _inc(a):
            return a + 1

        _inc_jit = jax.jit(_inc)

        def train(steps, x):
            for _ in range(steps):
                x = _inc_jit(x)
            return x
        """,
    ),
    "static-argnames-mismatch": (
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("sizes",))
        def pad(x, size):
            return x[:size]
        """,
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("size",))
        def pad(x, size):
            return x[:size]
        """,
    ),
    "rng-key-reuse": (
        """
        import jax

        def sample(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.uniform(key, (2,))
            return a + b
        """,
        """
        import jax

        def sample(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (2,))
            b = jax.random.uniform(k2, (2,))
            return a + b
        """,
    ),
    "collective-order": (
        """
        import jax

        def epoch_sum(x):
            if jax.process_index() == 0:
                total = jax.lax.psum(x, "data")
                return total
            return x
        """,
        """
        import jax

        def epoch_sum(x):
            total = jax.lax.psum(x, "data")
            if jax.process_index() == 0:
                print("sum ready")
            return total
        """,
    ),
    "donated-arg-reuse": (
        """
        import jax

        def run(step_fn, state, batch):
            step = jax.jit(step_fn, donate_argnums=(0,))
            new_state, metrics = step(state, batch)
            drift = state.mean()
            return new_state, metrics, drift
        """,
        """
        import jax

        def run(step_fn, state, batch):
            step = jax.jit(step_fn, donate_argnums=(0,))
            state, metrics = step(state, batch)
            drift = state.mean()
            return state, metrics, drift
        """,
    ),
    "broad-except": (
        """
        def load(path):
            try:
                return open(path).read()
            except Exception:
                return None
        """,
        """
        def load(path):
            try:
                return open(path).read()
            except OSError as e:
                print(f"unreadable {path}: {e}")
                return None
        """,
    ),
    "debug-in-hot-path": (
        """
        import jax

        @jax.jit
        def step(x):
            jax.debug.print("x = {}", x)
            return x * 2
        """,
        """
        import jax

        @jax.jit
        def step(x):
            return x * 2

        def debug_step(x):
            y = step(x)
            print("y =", y)
            return y
        """,
    ),
    "unhashable-width-overrides": (
        """
        def rebuild(model_cls, plan):
            ov = {name: int(n) for name, n in plan.width_overrides.items()}
            direct = model_cls(width_overrides={"conv1": 8})
            via_name = model_cls(width_overrides=ov)
            return direct, via_name
        """,
        """
        from turboprune_tpu.models import create_model

        def rebuild(model_cls, plan):
            ov = {name: int(n) for name, n in plan.width_overrides.items()}
            ov = tuple(sorted(ov.items()))
            normalized = model_cls(width_overrides=ov)
            # create_model normalizes a raw dict itself — the one callee
            # a dict may flow into.
            factory = create_model("vgg16", width_overrides={"conv1": 8})
            return normalized, factory
        """,
    ),
    # ---- PR 12: dtype-flow rules ------------------------------------
    "silent-upcast": (
        """
        import jax
        import jax.numpy as jnp

        # graftlint: dtype-policy=bf16
        @jax.jit
        def step(x):
            scale = jnp.float32(2.0)
            return jnp.mean(x * scale)
        """,
        """
        import jax
        import jax.numpy as jnp

        # graftlint: dtype-policy=bf16
        @jax.jit
        def step(x):
            # weak python literal promotes DOWN to bf16 — fine; and the
            # accumulation dtype is explicit — fine.
            return jnp.mean(x * 2.0, dtype=jnp.float32)
        """,
    ),
    "weak-type-promotion": (
        """
        import jax

        @jax.jit
        def scale_by(x, scale):
            return x * scale

        def warmup(x):
            return scale_by(x, 2)

        def train(x):
            return scale_by(x, 2.0)
        """,
        """
        import jax

        @jax.jit
        def scale_by(x, scale):
            return x * scale

        def warmup(x):
            return scale_by(x, 2.0)

        def train(x):
            return scale_by(x, 3.0)
        """,
    ),
    "scan-carry-dtype-drift": (
        """
        import jax.numpy as jnp
        from jax import lax

        def body(carry, x):
            new = (carry + x).astype(jnp.bfloat16)
            return new, x

        def run_chunk(xs):
            init = jnp.zeros((4,), jnp.float32)
            return lax.scan(body, init, xs)
        """,
        """
        import jax.numpy as jnp
        from jax import lax

        def body(carry, x):
            new = (carry + x).astype(jnp.float32)
            return new, x

        def run_chunk(xs):
            init = jnp.zeros((4,), jnp.float32)
            return lax.scan(body, init, xs)
        """,
    ),
    "missing-preferred-element-type": (
        """
        import jax
        import jax.numpy as jnp

        # graftlint: dtype-policy=bf16
        @jax.jit
        def project(a, b):
            return jnp.matmul(a, b)
        """,
        """
        import jax
        import jax.numpy as jnp

        # graftlint: dtype-policy=bf16
        @jax.jit
        def project(a, b):
            return jnp.matmul(a, b, preferred_element_type=jnp.float32)
        """,
    ),
    "cv-wait-no-predicate-loop": (
        """
        import threading

        class Mailbox:
            def __init__(self):
                self._cv = threading.Condition()
                self._items = []

            def get(self):
                with self._cv:
                    if not self._items:
                        self._cv.wait()
                    return self._items.pop()
        """,
        """
        import threading

        class Mailbox:
            def __init__(self):
                self._cv = threading.Condition()
                self._items = []

            def get(self):
                with self._cv:
                    while not self._items:
                        self._cv.wait()
                    return self._items.pop()
        """,
    ),
    "shape-varying-jit-arg": (
        """
        import jax

        @jax.jit
        def step(x):
            return x * 2

        def run(x, lengths):
            for n in lengths:
                x = step(x[:n])
            return x
        """,
        """
        import jax

        BUCKETS = (8, 32, 128)

        @jax.jit
        def step(x):
            return x * 2

        def run(x, idxs):
            for i in idxs:
                b = BUCKETS[i]
                x = step(x[:b])
            return x
        """,
    ),
    "concrete-shape-branch": (
        """
        import jax

        @jax.jit
        def forward(x):
            if x.shape[0] > 4:
                return x * 2
            return x
        """,
        """
        import jax

        @jax.jit
        def forward(x):
            return x * 2

        def dispatch(x):
            if x.shape[0] > 4:
                return forward(x)
            return x
        """,
    ),
    "bucket-set-escape": (
        """
        BUCKETS = (1, 8, 32)

        class Engine:
            def warmup(self):
                for b in BUCKETS:
                    self._executable(b)
                self._executable(64)
        """,
        """
        BUCKETS = (1, 8, 32)

        class Engine:
            def warmup(self):
                for b in BUCKETS:
                    self._executable(b)
                self._executable(32)
        """,
    ),
    "unpinned-donation-shape": (
        """
        import jax
        import jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def update(state, grad):
            return state + grad

        def run():
            a = update(jnp.zeros((4, 8)), jnp.ones((4, 8)))
            b = update(jnp.zeros((8, 8)), jnp.ones((8, 8)))
            return a, b
        """,
        """
        import jax
        import jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def update(state, grad):
            return state + grad

        def run():
            a = update(jnp.zeros((8, 8)), jnp.ones((8, 8)))
            b = update(jnp.zeros((8, 8)), jnp.ones((8, 8)))
            return a, b
        """,
    ),
    "rank-change-into-cache": (
        """
        import jax.numpy as jnp

        class Engine:
            def lookup(self, x):
                x = jnp.reshape(x, (-1,))
                return self._exec_cache[x.shape[0]]
        """,
        """
        import jax.numpy as jnp

        class Engine:
            def lookup(self, x):
                x = jnp.reshape(x, (-1,))
                return self._exec_cache[x.shape]
        """,
    ),
}


class TestRuleFixtures:
    def test_rule_count_meets_floor(self):
        assert len(RULES) >= 23
        assert set(FIXTURES) <= set(RULES)

    @pytest.mark.parametrize("rule_id", sorted(FIXTURES))
    def test_bad_snippet_caught(self, rule_id):
        bad, _ = FIXTURES[rule_id]
        hits = [f for f in run(bad) if f.rule == rule_id]
        assert hits, f"{rule_id} missed its bad fixture"
        # every finding carries a usable location + message
        for f in hits:
            assert f.line >= 1 and f.message and f.severity in (
                "error",
                "warning",
            )

    @pytest.mark.parametrize("rule_id", sorted(FIXTURES))
    def test_good_twin_silent(self, rule_id):
        _, good = FIXTURES[rule_id]
        hits = [f for f in run(good) if f.rule == rule_id]
        assert not hits, (
            f"{rule_id} false-positived on its good twin: "
            f"{[f.message for f in hits]}"
        )


class TestRuleEdgeCases:
    def test_host_sync_float_of_traced_param(self):
        src = """
        import jax

        @jax.jit
        def f(x):
            return float(x) * 2
        """
        assert "jit-host-sync" in rules_hit(src)

    def test_host_sync_float_of_static_is_fine(self):
        src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            return x / float(n) + x.shape[0]
        """
        assert "jit-host-sync" not in rules_hit(src)

    def test_host_sync_inside_scan_body(self):
        src = """
        import jax
        import numpy as np

        def epoch(state, batches):
            def body(s, b):
                return s, np.asarray(b)
            return jax.lax.scan(body, state, batches)
        """
        assert "jit-host-sync" in rules_hit(src)

    def test_shard_map_body_via_partial(self):
        src = """
        import jax
        from functools import partial
        from jax.experimental.shard_map import shard_map

        def kernel(x, axis_name):
            return jax.device_get(x)

        def run(mesh, x):
            fn = shard_map(
                partial(kernel, axis_name="data"),
                mesh=mesh, in_specs=None, out_specs=None,
            )
            return fn(x)
        """
        assert "jit-host-sync" in rules_hit(src)

    def test_retrace_jit_lower_in_function(self):
        src = """
        import jax

        def compile_bucket(fn, spec):
            return jax.jit(fn).lower(spec).compile()
        """
        assert "retrace-hazard" in rules_hit(src)

    def test_retrace_factory_return_is_fine(self):
        src = """
        import jax

        def make_step(fn, mesh):
            return jax.jit(fn, donate_argnums=(0,))
        """
        assert "retrace-hazard" not in rules_hit(src)

    def test_rng_fold_in_loop_is_fine(self):
        src = """
        import jax

        def draws(key, n):
            out = []
            for i in range(n):
                k = jax.random.fold_in(key, i)
                out.append(jax.random.normal(k, ()))
            return out
        """
        assert "rng-key-reuse" not in rules_hit(src)

    def test_rng_cross_iteration_reuse_caught(self):
        src = """
        import jax

        def draws(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(key, ()))
            return out
        """
        assert "rng-key-reuse" in rules_hit(src)

    def test_rng_early_return_dispatch_is_fine(self):
        src = """
        import jax

        def prune(method, masks, rng):
            if method == "a":
                return jax.random.bernoulli(rng, 0.5)
            if method == "b":
                return jax.random.normal(rng, (2,))
            return masks
        """
        assert "rng-key-reuse" not in rules_hit(src)

    def test_rng_numpy_generator_named_rng_is_fine(self):
        src = """
        import numpy as np

        def crop(img, rng):
            x = int(rng.integers(0, 4))
            y = int(rng.integers(0, 4))
            return img[y:, x:]
        """
        assert "rng-key-reuse" not in rules_hit(src)

    def test_rng_constant_key_in_library(self):
        src = "import jax\nKEY = jax.random.PRNGKey(0)\n"
        findings, _ = analyze_source(src, "lib/mod.py")
        assert any(f.rule == "rng-key-reuse" for f in findings)

    def test_rng_constant_key_in_tests_exempt(self):
        src = "import jax\nKEY = jax.random.PRNGKey(0)\n"
        findings, _ = analyze_source(src, "tests/test_mod.py")
        assert not any(f.rule == "rng-key-reuse" for f in findings)

    def test_collective_under_is_primary_wrapper(self):
        src = """
        from turboprune_tpu.parallel.multihost import broadcast_object, is_primary

        def share(obj):
            if is_primary():
                return broadcast_object(obj)
            return None
        """
        assert "collective-order" in rules_hit(src)

    def test_collective_process_count_guard_is_fine(self):
        src = """
        import jax
        from jax.experimental import multihost_utils

        def barrier():
            if jax.process_count() > 1:
                multihost_utils.sync_global_devices("b")
        """
        assert "collective-order" not in rules_hit(src)

    def test_donated_inline_jit_call(self):
        src = """
        import jax

        def run(fn, x):
            y = jax.jit(fn, donate_argnums=(0,))(x)
            return y + x
        """
        assert "donated-arg-reuse" in rules_hit(src)

    def test_donated_loop_rebind_is_fine(self):
        src = """
        import jax

        def run(fn, state, batches):
            step = jax.jit(fn, donate_argnums=(0,))
            for b in batches:
                state, m = step(state, b)
            return state
        """
        assert "donated-arg-reuse" not in rules_hit(src)

    def test_broad_except_with_reraise_is_fine(self):
        src = """
        def f():
            try:
                g()
            except Exception:
                cleanup()
                raise
        """
        assert "broad-except" not in rules_hit(src)

    def test_parse_error_is_a_finding(self):
        findings, _ = analyze_source("def broken(:\n", "lib/bad.py")
        assert [f.rule for f in findings] == ["parse-error"]


class TestWaivers:
    BAD = "def f():\n    try:\n        g()\n    except Exception:\n        return None\n"

    def test_inline_waiver_suppresses_with_reason(self):
        src = self.BAD.replace(
            "except Exception:",
            "except Exception:  # graftlint: disable=broad-except -- deliberate fallback",
        )
        findings, waivers = analyze_source(src, "lib/m.py")
        assert not [f for f in findings if not f.waived]
        (w,) = [f for f in findings if f.waived]
        assert w.waiver_reason == "deliberate fallback"
        assert all(wv.used for wv in waivers)

    def test_standalone_waiver_covers_next_line(self):
        src = self.BAD.replace(
            "    except Exception:",
            "    # graftlint: disable=broad-except -- next-line scope\n"
            "    except Exception:",
        )
        findings, _ = analyze_source(src, "lib/m.py")
        assert not [f for f in findings if not f.waived]

    def test_waiver_for_other_rule_does_not_suppress(self):
        src = self.BAD.replace(
            "except Exception:",
            "except Exception:  # graftlint: disable=jit-host-sync -- wrong rule",
        )
        findings, waivers = analyze_source(src, "lib/m.py")
        assert [f for f in findings if not f.waived]
        assert not any(w.used for w in waivers)

    def test_multi_rule_waiver(self):
        src = self.BAD.replace(
            "except Exception:",
            "except Exception:  # graftlint: disable=jit-host-sync,broad-except -- both",
        )
        findings, _ = analyze_source(src, "lib/m.py")
        assert not [f for f in findings if not f.waived]

    def test_reasonless_waiver_still_parses(self):
        src = self.BAD.replace(
            "except Exception:",
            "except Exception:  # graftlint: disable=broad-except",
        )
        findings, _ = analyze_source(src, "lib/m.py")
        (w,) = [f for f in findings if f.waived]
        assert w.waiver_reason is None

    def test_waiver_inside_string_literal_ignored(self):
        src = (
            's = "graftlint: disable=broad-except -- not a comment"\n'
            + self.BAD
        )
        findings, waivers = analyze_source(src, "lib/m.py")
        assert [f for f in findings if not f.waived]
        assert not waivers


class TestReportersAndCli:
    def _write(self, tmp_path, name, src):
        p = tmp_path / name
        p.write_text(textwrap.dedent(src))
        return p

    def test_json_reporter_shape(self, tmp_path):
        bad = self._write(tmp_path, "bad.py", FIXTURES["broad-except"][0])
        payload = json.loads(render_json(analyze_paths([bad])))
        assert payload["version"] == 2
        assert payload["files_analyzed"] == 1
        assert payload["summary"]["unwaived"] >= 1
        assert payload["summary"]["by_rule"].get("broad-except", 0) >= 1
        (f,) = [
            f
            for f in payload["findings"]
            if f["rule"] == "broad-except" and not f["waived"]
        ]
        assert set(f) == {
            "file",
            "line",
            "col",
            "rule",
            "severity",
            "message",
            "waived",
            "waiver_reason",
            "trace",
        }
        assert f["trace"] is None  # per-file findings carry no call path
        assert payload["unused_waivers"] == []

    def test_text_reporter_grepable(self, tmp_path):
        bad = self._write(tmp_path, "bad.py", FIXTURES["broad-except"][0])
        text = render_text(analyze_paths([bad]))
        assert f"{bad}:" in text and "broad-except" in text
        assert "graftlint: 1 finding(s)" in text

    def test_cli_exit_codes(self, tmp_path, capsys):
        bad = self._write(tmp_path, "bad.py", FIXTURES["broad-except"][0])
        good = self._write(tmp_path, "good.py", FIXTURES["broad-except"][1])
        assert cli_main([str(bad)]) == 1
        assert "broad-except" in capsys.readouterr().out
        assert cli_main([str(good)]) == 0
        assert cli_main(["--list-rules"]) == 0
        assert "jit-host-sync" in capsys.readouterr().out
        assert cli_main(["--select", "no-such-rule", str(good)]) == 2
        assert cli_main([str(tmp_path / "missing.py")]) == 2

    def test_cli_select_narrows(self, tmp_path, capsys):
        bad = self._write(tmp_path, "bad.py", FIXTURES["broad-except"][0])
        assert cli_main(["--select", "jit-host-sync", str(bad)]) == 0
        capsys.readouterr()


class TestSelfGate:
    """The rule set enforces itself on every future PR.

    Two layers: the per-file gate (unchanged from PR 2) and the PROJECT
    gate — the same ``--project turboprune_tpu conf tests`` invocation
    scripts/check.sh runs, covering the interprocedural rules and the
    config rules too. Stale-waiver accounting lives on the project gate
    because only project mode can fire every rule a waiver may name (a
    conf-dead-schema-field waiver in schema.py is invisible to the
    per-file pass by construction)."""

    @pytest.fixture(scope="class")
    def project_result(self):
        return analyze_project(
            [REPO / "turboprune_tpu", REPO / "conf", REPO / "tests"]
        )

    def test_package_and_tests_have_zero_unwaived_findings(self):
        result = analyze_paths(
            [REPO / "turboprune_tpu", REPO / "tests"]
        )
        msg = "\n".join(
            f"  {f.file}:{f.line}: [{f.rule}] {f.message}"
            for f in result.unwaived
        )
        assert not result.unwaived, (
            "graftlint found unwaived findings — fix them or add an "
            "inline '# graftlint: disable=<rule> -- reason' waiver:\n"
            + msg
        )

    def test_project_mode_has_zero_unwaived_findings(self, project_result):
        msg = "\n".join(
            f"  {f.file}:{f.line}: [{f.rule}] {f.message}"
            + (f"\n    call path: {' -> '.join(f.trace)}" if f.trace else "")
            for f in project_result.unwaived
        )
        assert not project_result.unwaived, (
            "graftlint --project found unwaived findings — fix them or "
            "waive with a reason (YAML comments work in conf/):\n" + msg
        )

    def test_no_stale_waivers_per_file_scope(self):
        """Per-file mode must not report its OWN rules' waivers stale
        (project-scope conf-* waivers are excluded by design)."""
        result = analyze_paths(
            [REPO / "turboprune_tpu", REPO / "tests"]
        )
        stale = "\n".join(
            f"  {w.file}:{w.line}: {sorted(w.rules)}"
            for w in result.unused_waivers
        )
        assert not result.unused_waivers, (
            "waivers matching no finding (remove them, they mask "
            "nothing):\n" + stale
        )

    def test_no_stale_waivers_project(self, project_result):
        stale = "\n".join(
            f"  {w.file}:{w.line}: {sorted(w.rules)}"
            for w in project_result.unused_waivers
        )
        assert not project_result.unused_waivers, (
            "waivers matching no finding under --project (remove them, "
            "they mask nothing):\n" + stale
        )

    def test_every_package_waiver_has_a_reason(self, project_result):
        missing = [
            f"{w.file}:{w.line}"
            for w in project_result.waivers
            if not w.reason
            and str(REPO / "turboprune_tpu") in w.file
        ]
        assert not missing, (
            "package waivers must document WHY: " + ", ".join(missing)
        )

    def test_cli_project_gate_exits_zero(self, capsys):
        rc = cli_main(
            [
                "--project",
                str(REPO / "turboprune_tpu"),
                str(REPO / "conf"),
                str(REPO / "tests"),
            ]
        )
        out = capsys.readouterr().out
        assert rc == 0, out


# =================================================================
# PR 3: whole-project mode — interprocedural + config rule fixtures
# =================================================================


def write_project(tmp_path, files: dict) -> Path:
    """Materialize ``{relpath: source}`` under tmp_path/proj."""
    proj = tmp_path / "proj"
    for rel, src in files.items():
        p = proj / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return proj


def run_project(tmp_path, files: dict, paths=None):
    proj = write_project(tmp_path, files)
    result = analyze_project([proj] if paths is None else [proj / p for p in paths])
    return result


def unwaived(result, rule_id=None):
    out = [f for f in result.findings if not f.waived]
    if rule_id:
        out = [f for f in out if f.rule == rule_id]
    return out


# Every interprocedural upgrade: (rule, bad files, good files). Each pair
# spans TWO modules — the whole point is firing across the file boundary
# the per-file layer documents as its blind spot.
INTERPROC_FIXTURES = {
    "jit-host-sync": (
        {
            "pkg/__init__.py": "",
            "pkg/helpers.py": """
                import numpy as np

                def to_host(x):
                    return np.asarray(x)
            """,
            "pkg/main.py": """
                import jax
                from .helpers import to_host

                @jax.jit
                def step(state, batch):
                    return to_host(state) + batch
            """,
        },
        {
            "pkg/__init__.py": "",
            "pkg/helpers.py": """
                import jax.numpy as jnp

                def to_dev(x):
                    return jnp.asarray(x)
            """,
            "pkg/main.py": """
                import jax
                from .helpers import to_dev

                @jax.jit
                def step(state, batch):
                    return to_dev(state) + batch
            """,
        },
    ),
    "collective-order": (
        {
            "pkg/__init__.py": "",
            "pkg/ckpt.py": """
                import jax

                def barrier(name):
                    if jax.process_count() > 1:
                        from jax.experimental import multihost_utils
                        multihost_utils.sync_global_devices(name)

                def save_all(tree, path):
                    del tree, path
                    barrier("save")
            """,
            "pkg/main.py": """
                import jax
                from .ckpt import save_all

                def checkpoint(tree):
                    if jax.process_index() == 0:
                        save_all(tree, "/tmp/x")
            """,
        },
        {
            "pkg/__init__.py": "",
            "pkg/ckpt.py": """
                import jax

                def barrier(name):
                    if jax.process_count() > 1:
                        from jax.experimental import multihost_utils
                        multihost_utils.sync_global_devices(name)

                def save_all(tree, path):
                    del tree, path
                    barrier("save")
            """,
            "pkg/main.py": """
                import jax
                from .ckpt import save_all

                def checkpoint(tree):
                    # every host reaches the collective; only the print is
                    # rank-conditional
                    save_all(tree, "/tmp/x")
                    if jax.process_index() == 0:
                        print("saved")
            """,
        },
    ),
    "rng-key-reuse": (
        {
            "pkg/__init__.py": "",
            "pkg/samplers.py": """
                import jax

                def draw(k, shape=(2,)):
                    return jax.random.normal(k, shape)
            """,
            "pkg/main.py": """
                from .samplers import draw

                def sample(key):
                    a = draw(key)
                    b = draw(key)
                    return a + b
            """,
        },
        {
            "pkg/__init__.py": "",
            "pkg/samplers.py": """
                import jax

                def draw(k, shape=(2,)):
                    return jax.random.normal(k, shape)
            """,
            "pkg/main.py": """
                import jax
                from .samplers import draw

                def sample(key):
                    k1, k2 = jax.random.split(key)
                    a = draw(k1)
                    b = draw(k2)
                    return a + b
            """,
        },
    ),
    "donated-arg-reuse": (
        {
            "pkg/__init__.py": "",
            "pkg/mesh.py": """
                import jax

                def make_step(fn):
                    return jax.jit(fn, donate_argnums=(0,))
            """,
            "pkg/main.py": """
                from .mesh import make_step

                def run(fn, state, batch):
                    step = make_step(fn)
                    new_state, metrics = step(state, batch)
                    drift = state.mean()
                    return new_state, metrics, drift
            """,
        },
        {
            "pkg/__init__.py": "",
            "pkg/mesh.py": """
                import jax

                def make_step(fn):
                    return jax.jit(fn, donate_argnums=(0,))
            """,
            "pkg/main.py": """
                from .mesh import make_step

                def run(fn, state, batch):
                    step = make_step(fn)
                    state, metrics = step(state, batch)
                    drift = state.mean()
                    return state, metrics, drift
            """,
        },
    ),
    "retrace-hazard": (
        {
            "pkg/__init__.py": "",
            "pkg/factory.py": """
                import jax

                def compile_step(fn):
                    return jax.jit(fn)
            """,
            "pkg/main.py": """
                from .factory import compile_step

                def train(fn, batches, x):
                    for b in batches:
                        step = compile_step(fn)
                        x = step(x, b)
                    return x
            """,
        },
        {
            "pkg/__init__.py": "",
            "pkg/factory.py": """
                import jax

                def compile_step(fn):
                    return jax.jit(fn)
            """,
            "pkg/main.py": """
                from .factory import compile_step

                def train(fn, batches, x):
                    step = compile_step(fn)
                    for b in batches:
                        x = step(x, b)
                    return x
            """,
        },
    ),
}


class TestInterprocFixtures:
    @pytest.mark.parametrize("rule_id", sorted(INTERPROC_FIXTURES))
    def test_bad_caught_across_modules(self, rule_id, tmp_path):
        bad, _ = INTERPROC_FIXTURES[rule_id]
        result = run_project(tmp_path, bad)
        hits = unwaived(result, rule_id)
        assert hits, f"{rule_id} missed its cross-module bad fixture"
        # an interprocedural finding must carry its call-path trace
        assert any(f.trace for f in hits), (
            f"{rule_id} fired without a trace: "
            f"{[(f.line, f.message) for f in hits]}"
        )

    @pytest.mark.parametrize("rule_id", sorted(INTERPROC_FIXTURES))
    def test_good_twin_silent(self, rule_id, tmp_path):
        _, good = INTERPROC_FIXTURES[rule_id]
        result = run_project(tmp_path, good)
        hits = unwaived(result, rule_id)
        assert not hits, (
            f"{rule_id} false-positived on its cross-module good twin: "
            f"{[(f.file, f.line, f.message) for f in hits]}"
        )

    def test_closure_factory_chain_spans_three_modules(self, tmp_path):
        """The flagship blind spot: a closure returned by one factory,
        jitted by another module's factory, reaching a host sync in a
        third module (train/steps.py -> parallel/mesh.py -> ops/*)."""
        files = {
            "pkg/__init__.py": "",
            "pkg/ops.py": """
                import numpy as np

                def pull(x):
                    return np.asarray(x)
            """,
            "pkg/steps.py": """
                from .ops import pull

                def make_train_step(model):
                    def train_step(state, batch):
                        return pull(state) + batch
                    return train_step
            """,
            "pkg/mesh.py": """
                import jax

                def make_sharded(train_step, mesh):
                    del mesh
                    return jax.jit(train_step, donate_argnums=(0,))
            """,
            "pkg/harness.py": """
                from .mesh import make_sharded
                from .steps import make_train_step

                def wire(model, mesh):
                    raw = make_train_step(model)
                    return make_sharded(raw, mesh)
            """,
        }
        result = run_project(tmp_path, files)
        hits = unwaived(result, "jit-host-sync")
        assert hits, "closure-factory jit entry not detected"
        (f,) = [h for h in hits if "ops.py" in h.file]
        assert f.trace and any("train_step" in hop for hop in f.trace)
        assert any("make_sharded" in hop for hop in f.trace)

    def test_interproc_finding_waivable_inline(self, tmp_path):
        bad, _ = INTERPROC_FIXTURES["jit-host-sync"]
        files = dict(bad)
        files["pkg/helpers.py"] = """
            import numpy as np

            def to_host(x):
                # trace-time constant pull, proven static
                # graftlint: disable=jit-host-sync -- trace-time constant; never a device tensor
                return np.asarray(x)
        """
        result = run_project(tmp_path, files)
        assert not unwaived(result, "jit-host-sync")
        waived = [
            f
            for f in result.findings
            if f.waived and f.rule == "jit-host-sync"
        ]
        assert waived and waived[0].waiver_reason.startswith("trace-time")

    def test_cached_factory_in_loop_is_fine(self, tmp_path):
        """An accessor with a cache-lookup early return (serve/engine.py's
        _executable) is NOT 'builds a fresh jit every call' — looping on
        it must stay silent."""
        files = {
            "pkg/__init__.py": "",
            "pkg/engine.py": """
                import jax

                _CACHE = {}

                def executable(fn, bucket):
                    hit = _CACHE.get(bucket)
                    if hit is not None:
                        return hit
                    compiled = jax.jit(fn)
                    _CACHE[bucket] = compiled
                    return compiled
            """,
            "pkg/main.py": """
                from .engine import executable

                def warmup(fn, buckets):
                    for b in buckets:
                        executable(fn, b)
            """,
        }
        result = run_project(tmp_path, files)
        assert not unwaived(result, "retrace-hazard")

    def test_self_method_resolution(self, tmp_path):
        """self.method() chains resolve: a collective buried two methods
        deep under a rank branch still fires."""
        files = {
            "pkg/__init__.py": "",
            "pkg/harness.py": """
                import jax

                class Harness:
                    def _barrier(self):
                        from jax.experimental import multihost_utils
                        multihost_utils.sync_global_devices("h")

                    def _save(self):
                        self._barrier()

                    def finish(self):
                        if jax.process_index() == 0:
                            self._save()
            """,
        }
        result = run_project(tmp_path, files)
        hits = unwaived(result, "collective-order")
        assert hits and any("_save" in (f.message or "") for f in hits)

    def test_reexport_chain_resolution(self, tmp_path):
        """Resolution follows package __init__ re-exports (the repo's
        `from .parallel import is_primary` idiom)."""
        files = {
            "pkg/__init__.py": "",
            "pkg/inner/__init__.py": """
                from .impl import save_all  # noqa: F401
            """,
            "pkg/inner/impl.py": """
                import jax

                def save_all(tree):
                    from jax.experimental import multihost_utils
                    multihost_utils.sync_global_devices("s")
            """,
            "pkg/main.py": """
                import jax
                from .inner import save_all

                def checkpoint(tree):
                    if jax.process_index() == 0:
                        save_all(tree)
            """,
        }
        result = run_project(tmp_path, files)
        assert unwaived(result, "collective-order")

    def test_per_file_findings_not_duplicated(self, tmp_path):
        """A site the lexical layer already flags yields exactly ONE
        finding in project mode, not a per-file + interproc pair."""
        files = {
            "pkg/__init__.py": "",
            "pkg/main.py": """
                import jax

                @jax.jit
                def step(state):
                    return state.sum().item()
            """,
        }
        result = run_project(tmp_path, files)
        hits = unwaived(result, "jit-host-sync")
        assert len(hits) == 1

    def test_project_text_report_shows_call_path(self, tmp_path):
        bad, _ = INTERPROC_FIXTURES["jit-host-sync"]
        proj = write_project(tmp_path, bad)
        text = render_text(analyze_project([proj]))
        assert "call path:" in text and "jit entry" in text


# ----------------------------------------------------------- config rules

SCHEMA_FIXTURE = """
    from dataclasses import dataclass, field

    METHODS = ("mag", "snip")


    class ConfigError(ValueError):
        pass


    def _check_choice(name, value, choices):
        if value not in choices:
            raise ConfigError(name)


    @dataclass
    class TrainConfig:
        lr: float = 0.1
        steps: int = 10
        method: str = "mag"
        resume: bool = False
        tag: str = ""

        def validate(self):
            _check_choice("train.method", self.method, METHODS)


    @dataclass
    class MainConfig:
        train: TrainConfig = field(default_factory=TrainConfig)
"""

# consumer reads every TrainConfig field + the group itself, so the
# dead-field rule stays quiet unless a fixture wants it to fire
CONSUMER_FIXTURE = """
    def use(cfg):
        t = cfg.train
        return (t.lr, t.steps, t.method, t.resume, t.tag)
"""


def conf_project(tmp_path, yamls: dict, schema=SCHEMA_FIXTURE, consumer=CONSUMER_FIXTURE):
    files = {"proj_pkg/__init__.py": "", "proj_pkg/schema.py": schema,
             "proj_pkg/consumer.py": consumer}
    for rel, src in yamls.items():
        files[f"conf/{rel}"] = src
    return run_project(tmp_path, files)


class TestConfRules:
    def test_conf_rule_registry(self):
        assert set(CONF_RULES) == {
            "conf-duplicate-key",
            "conf-unknown-key",
            "conf-bad-choice",
            "conf-type-mismatch",
            "conf-missing-group-file",
            "conf-dead-schema-field",
        }
        assert not (set(CONF_RULES) & set(RULES))

    # -- each rule: catching fixture + non-catching twin ------------------

    def test_unknown_key_caught(self, tmp_path):
        r = conf_project(tmp_path, {"train/bad.yaml": "lrr: 0.5\n"})
        (f,) = unwaived(r, "conf-unknown-key")
        assert "lrr" in f.message and f.line == 1

    def test_known_keys_silent(self, tmp_path):
        r = conf_project(
            tmp_path, {"train/good.yaml": "lr: 0.5\nsteps: 3\n"}
        )
        assert not unwaived(r, "conf-unknown-key")

    def test_bad_choice_caught(self, tmp_path):
        r = conf_project(tmp_path, {"train/bad.yaml": "method: bogus\n"})
        (f,) = unwaived(r, "conf-bad-choice")
        assert "bogus" in f.message and "mag" in f.message

    def test_good_choice_silent(self, tmp_path):
        r = conf_project(tmp_path, {"train/good.yaml": "method: snip\n"})
        assert not unwaived(r, "conf-bad-choice")

    def test_type_mismatch_caught(self, tmp_path):
        r = conf_project(
            tmp_path,
            {"train/bad.yaml": "steps: plenty\nresume: maybe\nlr: [1]\n"},
        )
        msgs = [f.message for f in unwaived(r, "conf-type-mismatch")]
        assert len(msgs) == 3
        assert any("steps" in m for m in msgs)
        assert any("resume" in m for m in msgs)
        assert any("lr" in m for m in msgs)

    def test_coercible_values_silent(self, tmp_path):
        # YAML-1.1 gotchas _coerce handles: 5e-4 reads as str, "true" as
        # str-bool, "5" as str-int — all coercible, none flagged
        r = conf_project(
            tmp_path,
            {
                "train/good.yaml": (
                    'lr: 5e-4\nsteps: "5"\nresume: "true"\ntag: x\n'
                )
            },
        )
        assert not unwaived(r, "conf-type-mismatch")

    def test_duplicate_key_caught(self, tmp_path):
        r = conf_project(
            tmp_path, {"train/bad.yaml": "lr: 0.1\nsteps: 2\nlr: 0.2\n"}
        )
        (f,) = unwaived(r, "conf-duplicate-key")
        assert f.line == 3 and "line 1" in f.message

    def test_unique_keys_silent(self, tmp_path):
        r = conf_project(
            tmp_path, {"train/good.yaml": "lr: 0.1\nsteps: 2\n"}
        )
        assert not unwaived(r, "conf-duplicate-key")

    def test_missing_group_file_caught(self, tmp_path):
        r = conf_project(
            tmp_path,
            {
                "top.yaml": "defaults:\n  - _self_\n  - train: nope\n",
                "train/good.yaml": "lr: 0.2\n",
            },
        )
        (f,) = unwaived(r, "conf-missing-group-file")
        assert "nope" in f.message

    def test_present_group_file_silent(self, tmp_path):
        r = conf_project(
            tmp_path,
            {
                "top.yaml": "defaults:\n  - _self_\n  - train: good\n",
                "train/good.yaml": "lr: 0.2\n",
            },
        )
        assert not unwaived(r, "conf-missing-group-file")

    def test_unknown_defaults_group_caught(self, tmp_path):
        r = conf_project(
            tmp_path,
            {"top.yaml": "defaults:\n  - _self_\n  - evals: whatever\n"},
        )
        assert unwaived(r, "conf-unknown-key")

    def test_toplevel_inline_group_values_checked(self, tmp_path):
        r = conf_project(
            tmp_path,
            {"top.yaml": "train:\n  method: bogus\n  typo: 1\n"},
        )
        assert unwaived(r, "conf-bad-choice")
        assert unwaived(r, "conf-unknown-key")

    def test_dead_schema_field_caught(self, tmp_path):
        consumer = """
            def use(cfg):
                t = cfg.train
                return (t.lr, t.steps, t.method, t.resume)
        """
        r = conf_project(
            tmp_path, {"train/good.yaml": "lr: 0.2\n"}, consumer=consumer
        )
        hits = unwaived(r, "conf-dead-schema-field")
        assert ["tag" in f.message for f in hits] == [True]
        assert "schema.py" in hits[0].file

    def test_read_fields_silent(self, tmp_path):
        r = conf_project(tmp_path, {"train/good.yaml": "lr: 0.2\n"})
        assert not unwaived(r, "conf-dead-schema-field")

    # -- yaml waivers -----------------------------------------------------

    def test_yaml_inline_waiver(self, tmp_path):
        r = conf_project(
            tmp_path,
            {
                "train/w.yaml": (
                    "method: bogus  "
                    "# graftlint: disable=conf-bad-choice -- migration: "
                    "option lands next PR\n"
                )
            },
        )
        assert not unwaived(r, "conf-bad-choice")
        waived = [f for f in r.findings if f.waived]
        assert waived and waived[0].waiver_reason.startswith("migration")
        assert not r.unused_waivers

    def test_yaml_standalone_waiver_covers_next_line(self, tmp_path):
        r = conf_project(
            tmp_path,
            {
                "train/w.yaml": (
                    "# graftlint: disable=conf-bad-choice -- staged\n"
                    "method: bogus\n"
                )
            },
        )
        assert not unwaived(r, "conf-bad-choice")

    def test_stale_yaml_waiver_reported_in_project_mode(self, tmp_path):
        r = conf_project(
            tmp_path,
            {
                "train/w.yaml": (
                    "method: snip  "
                    "# graftlint: disable=conf-bad-choice -- obsolete\n"
                )
            },
        )
        assert r.unused_waivers

    def test_conf_only_waiver_not_stale_per_file(self, tmp_path):
        """A Python-side waiver naming only conf-* rules is out of scope
        for per-file mode and must NOT be called stale there."""
        p = tmp_path / "m.py"
        p.write_text(
            "X = 1  # graftlint: disable=conf-dead-schema-field -- project-scope\n"
        )
        result = analyze_paths([p])
        assert not result.unused_waivers

    # -- select / CLI integration ----------------------------------------

    def test_select_narrows_conf_rules(self, tmp_path):
        proj = write_project(
            tmp_path,
            {
                "proj_pkg/__init__.py": "",
                "proj_pkg/schema.py": SCHEMA_FIXTURE,
                "proj_pkg/consumer.py": CONSUMER_FIXTURE,
                "conf/train/bad.yaml": "method: bogus\ntypo: 1\n",
            },
        )
        r = analyze_project([proj], select=["conf-bad-choice"])
        assert unwaived(r, "conf-bad-choice")
        assert not unwaived(r, "conf-unknown-key")

    def test_cli_select_accepts_conf_rule(self, tmp_path, capsys):
        proj = write_project(
            tmp_path,
            {
                "proj_pkg/__init__.py": "",
                "proj_pkg/schema.py": SCHEMA_FIXTURE,
                "proj_pkg/consumer.py": CONSUMER_FIXTURE,
                "conf/train/bad.yaml": "method: bogus\n",
            },
        )
        rc = cli_main(
            ["--project", "--select", "conf-bad-choice", str(proj)]
        )
        assert rc == 1
        assert "conf-bad-choice" in capsys.readouterr().out

    def test_cli_project_and_changed_mutually_exclusive(self, capsys):
        assert cli_main(["--project", "--changed"]) == 2
        capsys.readouterr()

    def test_cli_changed_uses_git_diff(self, tmp_path, monkeypatch):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(FIXTURES["broad-except"][0]))
        import turboprune_tpu.analysis.cli as cli_mod

        monkeypatch.setattr(
            cli_mod, "_changed_python_files", lambda base: [str(bad)]
        )
        assert cli_mod.main(["--changed"]) == 1
        monkeypatch.setattr(
            cli_mod, "_changed_python_files", lambda base: []
        )
        assert cli_mod.main(["--changed"]) == 0


# =================================================================
# Shape-flow lattice: edge cases the shape-rule FIXTURES don't pin
# =================================================================


class TestShapeLattice:
    """ScopeShapes/lattice semantics: the honest-`?` contract under
    partial knowledge — folds only happen when everything is known."""

    @staticmethod
    def _returns(src, seed=None):
        import ast

        from turboprune_tpu.analysis.shape_flow import ScopeShapes

        tree = ast.parse(textwrap.dedent(src))
        fn = next(
            n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)
        )
        return [v for _, v in ScopeShapes(fn, seed=seed).returns]

    def test_reshape_minus_one_folds_only_when_total_known(self):
        from turboprune_tpu.analysis.shape_flow import DIM_UNKNOWN, ArrayVal

        (v,) = self._returns(
            """
            def f():
                x = jnp.zeros((4, 8))
                return x.reshape(2, -1)
            """
        )
        assert v.shape == (2, 16)
        # one unknown dim poisons the product: -1 must stay honest
        (v,) = self._returns(
            "def f(x):\n    return x.reshape(-1)\n",
            seed={"x": ArrayVal((8, "n"), "x")},
        )
        assert v.shape == (DIM_UNKNOWN,)

    def test_broadcast_disagreement_collapses_to_unknown(self):
        from turboprune_tpu.analysis.shape_flow import (
            DIM_UNKNOWN,
            ArrayVal,
            broadcast_shapes,
        )

        assert broadcast_shapes((4, 8), (3, 8)) == (DIM_UNKNOWN, 8)
        assert broadcast_shapes((1, 8), (5, 8)) == (5, 8)
        assert broadcast_shapes(("n", 8), ("n", 8)) == ("n", 8)
        assert broadcast_shapes(("n", 8), (4, 8)) == (DIM_UNKNOWN, 8)
        # through the interpreter: a known-1 dim yields, symbols survive
        (v,) = self._returns(
            "def f(a, b):\n    return a + b\n",
            seed={
                "a": ArrayVal((4, 1), "a"),
                "b": ArrayVal((4, "k"), "b"),
            },
        )
        assert v.shape == (4, "k")

    def test_branch_join_collapses_disagreeing_dim(self):
        from turboprune_tpu.analysis.shape_flow import DIM_UNKNOWN

        (v,) = self._returns(
            """
            def f(flag):
                if flag:
                    x = jnp.zeros((4, 8))
                else:
                    x = jnp.zeros((6, 8))
                return x
            """
        )
        assert v.shape == (DIM_UNKNOWN, 8)

    def test_scan_carry_keeps_init_shape_ys_stay_unknown(self):
        carry, ys = self._returns(
            """
            def f(xs):
                init = jnp.zeros((4, 8))
                carry, ys = jax.lax.scan(step, init, xs)
                return carry
                return ys
            """
        )
        # dead second return is fine for the interpreter: both collect
        assert carry.shape == (4, 8)  # rank-stable across every step
        assert ys is None  # stacked ys: honestly untracked

    def test_concatenate_mixed_known_and_unknown_dims(self):
        from turboprune_tpu.analysis.shape_flow import DIM_UNKNOWN, ArrayVal

        (v,) = self._returns(
            "def f(a, b):\n    return jnp.concatenate((a, b))\n",
            seed={
                "a": ArrayVal((3, 8), "a"),
                "b": ArrayVal((4, 8), "b"),
            },
        )
        assert v.shape == (7, 8)  # both known: the axis dim folds
        (v,) = self._returns(
            "def f(a, b):\n    return jnp.concatenate((a, b))\n",
            seed={
                "a": ArrayVal((4, 8), "a"),
                "b": ArrayVal(("n", 8), "b"),
            },
        )
        # unknown contribution poisons ONLY the concat axis; the joined
        # non-axis dim stays known
        assert v.shape == (DIM_UNKNOWN, 8)


# =================================================================
# PR 12: dtype-flow analysis, SARIF, merge-base --changed, jaxpr audit
# =================================================================


class TestDtypeFlowEdgeCases:
    """Lattice/policy semantics the bad/good FIXTURES pairs don't pin."""

    def test_policy_comment_below_decorator_also_applies(self):
        src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        # graftlint: dtype-policy=bf16
        def step(x):
            return jnp.mean(x)
        """
        assert "silent-upcast" in rules_hit(src)

    def test_fp32_policy_opts_out_of_lexical_markers(self):
        """A declared full-precision policy beats the bf16-names-in-body
        heuristic — the triage escape hatch for fp32 code that merely
        MENTIONS bfloat16."""
        src = """
        import jax
        import jax.numpy as jnp

        # graftlint: dtype-policy=fp32
        @jax.jit
        def step(x):
            h = x.astype(jnp.bfloat16)
            return jnp.mean(h)
        """
        assert "silent-upcast" not in rules_hit(src)

    def test_lexical_bf16_marker_triggers_without_policy(self):
        src = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def step(x):
            h = x.astype(jnp.bfloat16)
            return np.tanh(h)
        """
        hits = [f for f in run(src) if f.rule == "silent-upcast"]
        assert hits and "np.tanh" in hits[0].message

    def test_per_def_policies_are_independent(self):
        src = """
        import jax
        import jax.numpy as jnp

        # graftlint: dtype-policy=bf16
        @jax.jit
        def reduced(x):
            return jnp.mean(x)

        @jax.jit
        def full(x):
            return jnp.mean(x)
        """
        hits = [f for f in run(src) if f.rule == "silent-upcast"]
        assert len(hits) == 1

    def test_np_dtype_constructor_is_explicit_not_host_compute(self):
        """np.float32(...) states a dtype; only the MIX with a reduced
        operand fires, as arithmetic, not as np-host-compute."""
        src = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        # graftlint: dtype-policy=bf16
        @jax.jit
        def step(x):
            scale = np.float32(0.5)
            return x * scale
        """
        hits = [f for f in run(src) if f.rule == "silent-upcast"]
        assert len(hits) == 1
        assert "arithmetic mixes" in hits[0].message

    def test_unknown_dtypes_stay_silent(self):
        src = """
        import jax
        import jax.numpy as jnp

        # graftlint: dtype-policy=bf16
        @jax.jit
        def step(x, helper):
            return x * helper(x)
        """
        assert "silent-upcast" not in rules_hit(src)

    def test_scan_drift_via_functools_partial(self):
        src = """
        import functools
        import jax.numpy as jnp
        from jax import lax

        def body(model, carry, x):
            return (carry + x).astype(jnp.bfloat16), x

        def run_chunk(model, xs):
            init = jnp.zeros((4,), jnp.float32)
            return lax.scan(functools.partial(body, model), init, xs)
        """
        assert "scan-carry-dtype-drift" in rules_hit(src)

    def test_scan_drift_via_lambda(self):
        src = """
        import jax.numpy as jnp
        from jax import lax

        def run_chunk(xs):
            init = jnp.zeros((4,), jnp.float32)
            return lax.scan(
                lambda c, x: ((c + x).astype(jnp.bfloat16), x), init, xs
            )
        """
        assert "scan-carry-dtype-drift" in rules_hit(src)

    def test_scan_weak_carry_out_adopts_init_dtype(self):
        src = """
        import jax.numpy as jnp
        from jax import lax

        def body(carry, x):
            return carry * 2.0, x

        def run_chunk(xs):
            init = jnp.zeros((4,), jnp.bfloat16)
            return lax.scan(body, init, xs)
        """
        assert "scan-carry-dtype-drift" not in rules_hit(src)

    def test_pet_einsum_skips_spec_string(self):
        src = """
        import jax
        import jax.numpy as jnp

        # graftlint: dtype-policy=bf16
        @jax.jit
        def project(a, b):
            return jnp.einsum("ij,jk->ik", a, b)
        """
        assert "missing-preferred-element-type" in rules_hit(src)

    def test_pet_silent_on_full_precision_operands(self):
        src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def project(a, b):
            return jnp.matmul(a, b)
        """
        assert "missing-preferred-element-type" not in rules_hit(src)

    def test_dtype_rules_skip_test_files(self):
        bad, _ = FIXTURES["silent-upcast"]
        findings, _ = analyze_source(
            textwrap.dedent(bad), "tests/test_mixed.py"
        )
        assert not [f for f in findings if f.rule == "silent-upcast"]


class TestDtypeInterproc:
    """The dtype seeding must cross module boundaries with a call path."""

    FILES = {
        "pkg/__init__.py": "",
        "pkg/helpers.py": """
            import jax.numpy as jnp

            def fancy_norm(h):
                return jnp.mean(h)

            def project(a, b):
                return jnp.matmul(a, b)
            """,
        "pkg/step.py": """
            import jax

            from .helpers import fancy_norm, project


            # graftlint: dtype-policy=bf16
            @jax.jit
            def train_step(x, w):
                h = project(x, w)
                return fancy_norm(h)
            """,
    }

    def test_helper_findings_fire_across_modules_with_trace(self, tmp_path):
        r = run_project(tmp_path, self.FILES)
        upcasts = unwaived(r, "silent-upcast")
        pets = unwaived(r, "missing-preferred-element-type")
        assert upcasts and "helpers.py" in upcasts[0].file
        assert pets and "helpers.py" in pets[0].file
        for f in upcasts + pets:
            assert f.trace and "reduced jit entry" in f.trace[0]
            assert "train_step" in f.trace[0]

    def test_full_precision_entry_does_not_seed_helpers(self, tmp_path):
        files = dict(self.FILES)
        files["pkg/step.py"] = files["pkg/step.py"].replace(
            "# graftlint: dtype-policy=bf16", ""
        )
        r = run_project(tmp_path, files)
        assert not unwaived(r, "silent-upcast")
        assert not unwaived(r, "missing-preferred-element-type")


class TestScanRegionClassification:
    """Satellite: lax.scan bodies passed as functools.partial or resolved
    from an enclosing scope classify as traced regions — with the bound
    leading params static and the carry traced."""

    def test_partial_bound_scan_body_carry_is_traced(self):
        src = """
        import functools
        import jax
        import numpy as np

        def body(model, carry, x):
            return carry, np.asarray(x)

        def epoch(model, state, batches):
            return jax.lax.scan(
                functools.partial(body, model), state, batches
            )
        """
        assert "jit-host-sync" in rules_hit(src)

    def test_partial_bound_scan_body_bound_param_is_static(self):
        """float() of the partial-BOUND leading param is a Python value
        at trace time; float() of the carry is a sync. Probes
        traced_params directly."""
        src = """
        import functools
        import jax

        def body(cfg, carry, x):
            scale = float(cfg)
            return carry * scale, x

        def epoch(cfg, state, batches):
            return jax.lax.scan(
                functools.partial(body, cfg), state, batches
            )
        """
        assert "jit-host-sync" not in rules_hit(src)

    def test_partial_bound_scan_body_carry_float_is_sync(self):
        src = """
        import functools
        import jax

        def body(cfg, carry, x):
            scale = float(carry)
            return carry * scale, x

        def epoch(cfg, state, batches):
            return jax.lax.scan(
                functools.partial(body, cfg), state, batches
            )
        """
        assert "jit-host-sync" in rules_hit(src)

    def test_closure_scan_body_is_traced(self):
        src = """
        import jax
        import numpy as np

        def epoch(model, state, batches):
            def body(carry, batch):
                out = model(batch)
                return carry + out, np.asarray(out)

            return jax.lax.scan(body, state, batches)
        """
        assert "jit-host-sync" in rules_hit(src)

    def test_closure_scan_body_without_sync_is_silent(self):
        src = """
        import jax

        def epoch(model, state, batches):
            def body(carry, batch):
                out = model(batch)
                return carry + out, out

            return jax.lax.scan(body, state, batches)
        """
        assert "jit-host-sync" not in rules_hit(src)


class TestWaiverScoping:
    """Satellite: stale-waiver accounting per scope. Conf-only waivers are
    project-scope (the per-file pass can never fire them); waivers naming
    ANY per-file rule stay in per-file stale accounting."""

    def test_py_rule_stale_waiver_flagged_per_file(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text("X = 1  # graftlint: disable=broad-except -- obsolete\n")
        result = analyze_paths([p])
        assert result.unused_waivers

    def test_mixed_py_and_conf_waiver_still_stale_per_file(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text(
            "X = 1  "
            "# graftlint: disable=broad-except,conf-unknown-key -- obsolete\n"
        )
        result = analyze_paths([p])
        assert result.unused_waivers

    def test_conf_only_py_waiver_stale_in_project_mode(self, tmp_path):
        r = run_project(
            tmp_path,
            {
                "pkg/__init__.py": "",
                "pkg/m.py": (
                    "X = 1  "
                    "# graftlint: disable=conf-dead-schema-field -- gone\n"
                ),
            },
        )
        assert r.unused_waivers

    def test_changed_mode_uses_per_file_scoping(self, tmp_path):
        """analyze_files (the --changed path) must not false-flag a
        project-scope waiver either."""
        p = tmp_path / "m.py"
        p.write_text(
            "X = 1  # graftlint: disable=conf-dead-schema-field -- scope\n"
        )
        result = analyze_files([p])
        assert not result.unused_waivers


class TestChangedMergeBase:
    """Satellite: --changed diffs against the merge-base, not the tip of
    the base branch, and picks up untracked .py/.yaml files."""

    @staticmethod
    def _git(cwd, *args):
        import subprocess

        subprocess.run(
            ["git", "-c", "user.name=t", "-c", "user.email=t@t"]
            + list(args),
            cwd=cwd,
            check=True,
            capture_output=True,
        )

    def test_merge_base_and_untracked(self, tmp_path, monkeypatch):
        import turboprune_tpu.analysis.cli as cli_mod

        repo = tmp_path / "r"
        repo.mkdir()
        g = lambda *a: self._git(repo, *a)  # noqa: E731
        g("init", "-q")
        (repo / "a.py").write_text("A = 1\n")
        g("add", "a.py")
        g("commit", "-qm", "init")
        g("branch", "-M", "main")
        g("checkout", "-qb", "feature")
        (repo / "b.py").write_text("B = 2\n")
        g("add", "b.py")
        g("commit", "-qm", "feature work")
        # advance main past the branch point: its diff vs the feature
        # worktree must NOT leak into --changed
        g("checkout", "-q", "main")
        (repo / "a.py").write_text("A = 99\n")
        g("commit", "-aqm", "main moved on")
        g("checkout", "-q", "feature")
        (repo / "c.yaml").write_text("k: v\n")  # untracked, lintable
        (repo / "c.txt").write_text("notes\n")  # untracked, not lintable

        monkeypatch.chdir(repo)
        files = cli_mod._changed_python_files("main")
        assert "b.py" in files
        assert "c.yaml" in files
        assert "a.py" not in files
        assert "c.txt" not in files

    def test_changed_routes_yaml_through_conf_rules(self, tmp_path):
        y = tmp_path / "train.yaml"
        y.write_text("lr: 0.1\nlr: 0.2\n")
        result = analyze_files([y])
        assert [f for f in result.unwaived if f.rule == "conf-duplicate-key"]
        assert result.files_analyzed == 1


class TestSarifReporter:
    def _result(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text(
            textwrap.dedent(
                """
                import jax

                @jax.jit
                def step(x):
                    return x.item()

                @jax.jit
                def step2(x):
                    # graftlint: disable=jit-host-sync -- pinned fixture
                    return x.item()
                """
            )
        )
        return p

    def test_sarif_shape_and_suppressions(self, tmp_path, capsys):
        p = self._result(tmp_path)
        rc = cli_main([str(p), "--format", "sarif"])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["version"] == "2.1.0"
        runrec = doc["runs"][0]
        assert runrec["tool"]["driver"]["name"] == "graftlint"
        rules = {r["id"] for r in runrec["tool"]["driver"]["rules"]}
        assert "jit-host-sync" in rules
        results = runrec["results"]
        assert len(results) == 2
        suppressed = [r for r in results if "suppressions" in r]
        live = [r for r in results if "suppressions" not in r]
        assert len(suppressed) == 1 and len(live) == 1
        assert suppressed[0]["suppressions"][0]["kind"] == "inSource"
        assert "pinned fixture" in (
            suppressed[0]["suppressions"][0]["justification"]
        )
        loc = live[0]["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("bad.py")
        assert loc["region"]["startLine"] >= 1

    def test_format_json_matches_json_flag(self, tmp_path, capsys):
        p = self._result(tmp_path)
        cli_main([str(p), "--format", "json"])
        via_format = capsys.readouterr().out
        cli_main([str(p), "--json"])
        via_flag = capsys.readouterr().out
        assert json.loads(via_format) == json.loads(via_flag)

    def test_help_documents_exit_codes_and_modes(self):
        text = build_parser().format_help()
        assert "exit codes" in text
        for marker in ("--jaxpr-audit", "--format", "merge-base"):
            assert marker in text


class TestJaxprAudit:
    """--jaxpr-audit on tiny synthetic entries (the full train-step audit
    runs in scripts/check.sh; here we pin the diff semantics)."""

    PLANTED = textwrap.dedent(
        """
        import jax
        import jax.numpy as jnp
        import numpy as np


        @jax.jit
        def step(x):
            h = x.astype(jnp.bfloat16)
            y = h * np.float32(2.0)
            return y.sum()


        def entry():
            return step, (jnp.ones((4, 4), jnp.float32),)
        """
    )

    def test_planted_upcast_caught_statically_and_in_jaxpr(
        self, tmp_path, capsys
    ):
        pytest.importorskip("jax")
        # statically: the bf16*f32 mix is a silent-upcast finding
        findings, _ = analyze_source(self.PLANTED, "lib/planted.py")
        assert [f for f in findings if f.rule == "silent-upcast"]
        # dynamically: the same line shows up as a reduced->wide convert
        p = tmp_path / "planted.py"
        p.write_text(self.PLANTED)
        rc = cli_main(["--jaxpr-audit", f"{p}:entry"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "bfloat16 -> float32" in out
        assert "[finding]" in out
        assert "NOT clean" in out

    def test_explicit_cast_audits_clean(self, tmp_path, capsys):
        pytest.importorskip("jax")
        src = textwrap.dedent(
            """
            import jax
            import jax.numpy as jnp


            # graftlint: dtype-policy=bf16
            @jax.jit
            def step(x):
                h = x.astype(jnp.bfloat16)
                y = h.astype(jnp.float32)
                return y.sum()


            def entry():
                return step, (jnp.ones((4, 4), jnp.float32),)
            """
        )
        p = tmp_path / "clean.py"
        p.write_text(src)
        rc = cli_main(["--jaxpr-audit", f"{p}:entry"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "[explicit-cast]" in out
        assert "jaxpr-audit: clean" in out

    def test_bad_entry_spec_is_usage_error(self, capsys):
        pytest.importorskip("jax")
        assert cli_main(["--jaxpr-audit", "nonsense"]) == 2
        assert "entry" in capsys.readouterr().err

    def test_missing_entry_file_is_usage_error(self, capsys):
        pytest.importorskip("jax")
        assert cli_main(["--jaxpr-audit", "/nonexistent/x.py:entry"]) == 2
        capsys.readouterr()

    def test_audit_mutually_exclusive_with_project(self, capsys):
        assert cli_main(["--project", "--jaxpr-audit"]) == 2
        capsys.readouterr()


# ----------------------------------------------- concurrency (PR 17)
# Every project-only thread rule: (bad files that MUST trigger it, good
# twin that MUST NOT). The pairs drive the full stack — thread-model
# discovery, lockset interpretation, and the interproc hook.
CONCURRENCY_FIXTURES = {
    "unsynchronized-shared-mutation": (
        {
            "pkg/__init__.py": "",
            "pkg/worker.py": """
                import threading

                class Counter:
                    def __init__(self):
                        self._thread = None
                        self.total = 0

                    def start(self):
                        self._thread = threading.Thread(target=self._run)
                        self._thread.start()

                    def _run(self):
                        for _ in range(100):
                            self.total = self.total + 1

                    def read(self):
                        return self.total
            """,
        },
        {
            "pkg/__init__.py": "",
            "pkg/worker.py": """
                import threading

                class Counter:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._thread = None
                        self.total = 0

                    def start(self):
                        self._thread = threading.Thread(target=self._run)
                        self._thread.start()

                    def _run(self):
                        for _ in range(100):
                            with self._lock:
                                self.total = self.total + 1

                    def read(self):
                        with self._lock:
                            return self.total
            """,
        },
    ),
    "lock-order-inversion": (
        {
            "pkg/__init__.py": "",
            "pkg/transfer.py": """
                import threading

                class Transfer:
                    def __init__(self):
                        self._audit = threading.Lock()
                        self._books = threading.Lock()

                    def deposit(self):
                        with self._audit:
                            with self._books:
                                return 1

                    def withdraw(self):
                        with self._books:
                            with self._audit:
                                return 2
            """,
        },
        {
            "pkg/__init__.py": "",
            "pkg/transfer.py": """
                import threading

                class Transfer:
                    def __init__(self):
                        self._audit = threading.Lock()
                        self._books = threading.Lock()

                    def deposit(self):
                        with self._audit:
                            with self._books:
                                return 1

                    def withdraw(self):
                        with self._audit:
                            with self._books:
                                return 2
            """,
        },
    ),
    "blocking-call-under-lock": (
        {
            "pkg/__init__.py": "",
            "pkg/refresh.py": """
                import threading
                from urllib.request import urlopen

                class Refresher:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.value = None

                    def refresh(self):
                        with self._lock:
                            self.value = self._fetch()

                    def _fetch(self):
                        return urlopen("http://example.com").read()
            """,
        },
        {
            "pkg/__init__.py": "",
            "pkg/refresh.py": """
                import threading
                from urllib.request import urlopen

                class Refresher:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.value = None

                    def refresh(self):
                        data = self._fetch()
                        with self._lock:
                            self.value = data

                    def _fetch(self):
                        return urlopen("http://example.com").read()
            """,
        },
    ),
    "check-then-act-race": (
        {
            "pkg/__init__.py": "",
            "pkg/cache.py": """
                import threading

                class Cache:
                    def __init__(self):
                        self._cache = {}
                        self._thread = None

                    def start(self):
                        self._thread = threading.Thread(target=self._refill)
                        self._thread.start()

                    def _refill(self):
                        self.get("warm")

                    def get(self, key):
                        if key not in self._cache:
                            self._cache[key] = len(key)
                        return self._cache[key]
            """,
        },
        {
            "pkg/__init__.py": "",
            "pkg/cache.py": """
                import threading

                class Cache:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._cache = {}
                        self._thread = None

                    def start(self):
                        self._thread = threading.Thread(target=self._refill)
                        self._thread.start()

                    def _refill(self):
                        self.get("warm")

                    def get(self, key):
                        with self._lock:
                            if key not in self._cache:
                                self._cache[key] = len(key)
                            return self._cache[key]
            """,
        },
    ),
}


class TestConcurrencyFixtures:
    def test_rules_registered_as_project_only(self):
        for rid in CONCURRENCY_FIXTURES:
            assert rid in RULES, rid
            assert RULES[rid].project_only, f"{rid} must be project-only"

    @pytest.mark.parametrize("rule_id", sorted(CONCURRENCY_FIXTURES))
    def test_bad_caught_with_trace(self, rule_id, tmp_path):
        bad, _ = CONCURRENCY_FIXTURES[rule_id]
        result = run_project(tmp_path, bad)
        hits = unwaived(result, rule_id)
        assert hits, f"{rule_id} missed its bad fixture"
        assert any(f.trace for f in hits), (
            f"{rule_id} fired without a thread/lock trace: "
            f"{[(f.line, f.message) for f in hits]}"
        )

    @pytest.mark.parametrize("rule_id", sorted(CONCURRENCY_FIXTURES))
    def test_good_twin_silent(self, rule_id, tmp_path):
        _, good = CONCURRENCY_FIXTURES[rule_id]
        result = run_project(tmp_path, good)
        hits = unwaived(result, rule_id)
        assert not hits, (
            f"{rule_id} false-positived on its good twin: "
            f"{[(f.file, f.line, f.message) for f in hits]}"
        )

    @pytest.mark.parametrize("rule_id", sorted(CONCURRENCY_FIXTURES))
    def test_project_only_rules_silent_per_file(self, rule_id):
        """The same bad source analyzed per-file must NOT fire: the
        thread rules need the project thread model and would be pure
        noise (or pure silence) per-file."""
        bad, _ = CONCURRENCY_FIXTURES[rule_id]
        for src in bad.values():
            findings, _w = analyze_source(
                textwrap.dedent(src), "lib/snippet.py"
            )
            assert not [f for f in findings if f.rule == rule_id]

    def test_self_deadlock_single_lock_cycle(self, tmp_path):
        files = {
            "pkg/__init__.py": "",
            "pkg/relock.py": """
                import threading

                class Box:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def outer(self):
                        with self._lock:
                            return self.inner()

                    def inner(self):
                        with self._lock:
                            return 1
            """,
        }
        hits = unwaived(
            run_project(tmp_path, files), "lock-order-inversion"
        )
        assert hits and "self-deadlock" in hits[0].message

    def test_rlock_reentry_is_silent(self, tmp_path):
        files = {
            "pkg/__init__.py": "",
            "pkg/relock.py": """
                import threading

                class Box:
                    def __init__(self):
                        self._lock = threading.RLock()

                    def outer(self):
                        with self._lock:
                            return self.inner()

                    def inner(self):
                        with self._lock:
                            return 1
            """,
        }
        assert not unwaived(
            run_project(tmp_path, files), "lock-order-inversion"
        )


class TestGuardedByContract:
    """# guarded-by: <lock> annotations switch the mutation rule from
    heuristic to contract mode: EVERY access outside __init__ must hold
    the named lock, spawning or not."""

    def _files(self, body):
        return {"pkg/__init__.py": "", "pkg/guarded.py": body}

    def test_violation_fires_without_any_spawn(self, tmp_path):
        files = self._files(
            """
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._entries = {}  # guarded-by: _lock

                def add(self, k, v):
                    with self._lock:
                        self._entries[k] = v

                def peek(self):
                    return self._entries
            """
        )
        hits = unwaived(
            run_project(tmp_path, files), "unsynchronized-shared-mutation"
        )
        assert hits
        assert "guarded-by" in hits[0].message
        assert "peek" in hits[0].message
        assert hits[0].trace

    def test_honored_contract_is_silent(self, tmp_path):
        files = self._files(
            """
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._entries = {}  # guarded-by: _lock

                def add(self, k, v):
                    with self._lock:
                        self._entries[k] = v

                def peek(self):
                    with self._lock:
                        return dict(self._entries)
            """
        )
        assert not unwaived(
            run_project(tmp_path, files), "unsynchronized-shared-mutation"
        )

    def test_inline_guard_does_not_leak_to_next_attribute(self, tmp_path):
        """Regression: an INLINE guard comment annotates only its own
        assignment; the attribute initialized on the next line must not
        inherit the contract (only a standalone comment line above an
        assignment annotates downward)."""
        files = self._files(
            """
            import threading

            class Pair:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._a = 0  # guarded-by: _lock
                    self._b = 0

                def bump_a(self):
                    with self._lock:
                        self._a = 1

                def bump_b(self):
                    self._b = 1
            """
        )
        assert not unwaived(
            run_project(tmp_path, files), "unsynchronized-shared-mutation"
        )

    def test_standalone_guard_line_above_applies(self, tmp_path):
        files = self._files(
            """
            import threading

            class Pair:
                def __init__(self):
                    self._lock = threading.Lock()
                    # guarded-by: _lock
                    self._a = 0

                def bump_a(self):
                    self._a = 1
            """
        )
        hits = unwaived(
            run_project(tmp_path, files), "unsynchronized-shared-mutation"
        )
        assert hits and "bump_a" in hits[0].message


class TestParallelProjectMode:
    """--jobs N: the per-file half of project mode fans out over a
    process pool; findings must be byte-identical to the serial run."""

    def _many_files(self, tmp_path):
        files = {"pkg/__init__.py": ""}
        for i in range(10):  # > core._MIN_PARALLEL_FILES
            files[f"pkg/mod{i}.py"] = f"""
                def load{i}(path):
                    try:
                        return open(path).read()
                    except Exception:
                        return None
            """
        return write_project(tmp_path, files)

    def _key(self, f):
        return (f.file, f.line, f.col, f.rule, f.message, f.waived)

    def test_jobs_do_not_change_findings_or_order(self, tmp_path):
        proj = self._many_files(tmp_path)
        serial = analyze_project([proj], jobs=1)
        parallel = analyze_project([proj], jobs=2)
        assert [self._key(f) for f in serial.findings] == [
            self._key(f) for f in parallel.findings
        ]
        assert len(serial.unwaived) == 10
        assert serial.files_analyzed == parallel.files_analyzed

    def test_cli_jobs_flag_parses(self):
        args = build_parser().parse_args(["--project", "--jobs", "2"])
        assert args.jobs == 2


# =================================================================
# Rule-docs generation + executable-set manifest + compile audit
# =================================================================


class TestRuleDocs:
    def test_every_rule_documents_why(self):
        """doc_why is load-bearing: it becomes the README catalog's third
        column. A rule without one ships an empty cell."""
        for rule in RULES.values():
            assert rule.doc_why, f"{rule.id} has no doc_why"
        for rule in CONF_RULES.values():
            assert rule.doc_why, f"{rule.id} has no doc_why"

    def test_readme_block_matches_generated(self):
        """The staleness self-gate: the marked block in README.md must be
        byte-identical to what --rule-docs generates from the registries."""
        from turboprune_tpu.analysis.reporters import render_rule_docs

        text = (REPO / "README.md").read_text(encoding="utf-8")
        begin = text.index("rule-docs:begin")
        begin = text.index("\n", begin) + 1
        end = text.index("<!-- rule-docs:end -->")
        assert text[begin:end] == render_rule_docs(), (
            "README rule catalog is stale — regenerate with "
            "`python -m turboprune_tpu.analysis --rule-docs` and paste it "
            "between the rule-docs markers"
        )

    def test_rule_docs_covers_every_registered_rule(self):
        from turboprune_tpu.analysis.reporters import render_rule_docs

        docs = render_rule_docs()
        for rid in list(RULES) + list(CONF_RULES):
            assert f"`{rid}`" in docs

    def test_rule_docs_cli(self, capsys):
        assert cli_main(["--rule-docs"]) == 0
        out = capsys.readouterr().out
        assert out.startswith("| Rule | Severity | Catches |")


class TestExecManifest:
    def test_build_is_deterministic_and_repo_relative(self):
        from turboprune_tpu.analysis.exec_manifest import build_manifest

        m1, m2 = build_manifest(), build_manifest()
        assert m1 == m2
        for e in m1["entries"] + m1["compile_sites"]:
            assert not Path(e["file"]).is_absolute()
            assert "\\" not in e["file"]

    def test_manifest_knows_the_serving_surface(self):
        from turboprune_tpu.analysis.exec_manifest import (
            build_manifest,
            executable_names,
        )

        m = build_manifest()
        assert set(m["plan_kinds"]) == {"compact", "masked", "mixed", "nm"}
        assert set(m["buckets"]) == {1, 8, 32, 128}
        names = executable_names(m)
        # the factory-resolved eval step and the engine's jit target
        assert {"train_step", "eval_step", "_apply"} <= names
        # the engine's declared bucket table is one of the bucket sets
        assert any(
            k.endswith("serve/engine.py:DEFAULT_BUCKETS")
            for k in m["bucket_sets"]
        )

    def test_covers_contract(self):
        from turboprune_tpu.analysis.exec_manifest import covers

        m = {"plan_kinds": {"masked": "x:1"}, "buckets": [1, 8]}
        assert covers(m, "masked", 8)
        assert not covers(m, "masked", 4)  # undeclared bucket
        assert not covers(m, "compact", 8)  # undeclared plan kind

    def test_checked_in_manifest_diff_clean(self, capsys):
        """The check.sh round-trip stage, as a test: the committed JSON
        must match a fresh build (exit 1 + itemized drift otherwise)."""
        from turboprune_tpu.analysis.exec_manifest import run_exec_manifest

        assert run_exec_manifest("diff") == 0
        assert "clean" in capsys.readouterr().out

    def test_diff_itemizes_drift(self, tmp_path, capsys, monkeypatch):
        import turboprune_tpu.analysis.exec_manifest as em

        stale = json.loads(
            json.dumps(em.load_manifest() or em.build_manifest())
        )
        stale["buckets"] = [1, 8]
        stale["plan_kinds"].pop("nm", None)
        p = tmp_path / "exec_manifest.json"
        p.write_text(json.dumps(stale))
        monkeypatch.setattr(em, "MANIFEST_PATH", p)
        assert em.run_exec_manifest("diff") == 1
        out = capsys.readouterr().out
        assert "nm" in out and "drift" in out.lower()

    def test_a_moved_line_is_no_drift_and_a_renamed_entry_is(self, tmp_path, capsys, monkeypatch):
        """The lockfile locks the set (entries by file, name and reason,
        sites by file and target, plan kinds by file), not where in a file a
        body stands: the checked-in file holds no line number, and one that
        does, every line off by one, still diffs clean."""
        import turboprune_tpu.analysis.exec_manifest as em

        for row in em.load_manifest()["entries"] + em.load_manifest()["compile_sites"]:
            assert not {"line", "end"} & set(row)
        moved = em.build_manifest()
        for row in moved["entries"] + moved["compile_sites"]:
            row.update({k: row[k] + 1 for k in ("line", "end") if k in row})
        moved["plan_kinds"] = {k: f"{v.partition(':')[0]}:1" for k, v in moved["plan_kinds"].items()}
        p = tmp_path / "exec_manifest.json"
        monkeypatch.setattr(em, "MANIFEST_PATH", p)
        p.write_text(json.dumps(moved))
        assert em.run_exec_manifest("diff") == 0
        assert "clean" in capsys.readouterr().out
        moved["entries"][0]["name"] += "_renamed"
        p.write_text(json.dumps(moved))
        assert em.run_exec_manifest("diff") == 1
        out = capsys.readouterr().out
        assert "_renamed" in out and "+ entries" in out and "- entries" in out

    def test_unknown_mode_is_usage_error(self):
        from turboprune_tpu.analysis.exec_manifest import run_exec_manifest

        with pytest.raises(ValueError, match="bogus"):
            run_exec_manifest("bogus")


class TestCompileAudit:
    def test_runtime_name_mangles_like_jax(self):
        from turboprune_tpu.analysis.compile_audit import _runtime_name

        assert _runtime_name("train_step") == "jit_train_step"
        assert _runtime_name("<lambda>") == "jit__lambda_"
        assert _runtime_name("_apply") == "jit__apply"

    def test_unknown_target_is_usage_error(self):
        from turboprune_tpu.analysis.compile_audit import (
            AuditError,
            run_compile_audit,
        )

        with pytest.raises(AuditError, match="bogus"):
            run_compile_audit("bogus-target")

    def test_ledger_records_a_real_compile(self):
        """The ledger patches a jax-internal funnel. If jax moves it, the
        audit must fail here, not report "0 compiles" for ever after."""
        import jax
        import jax.numpy as jnp

        from turboprune_tpu.analysis.compile_audit import CompileLedger

        def tiny_step(x):
            return x * 2.0 + 1.0

        x = jnp.zeros((3,), jnp.float32)  # created outside the window
        ledger = CompileLedger()
        with ledger:
            jax.jit(tiny_step)(x).block_until_ready()
        assert [r["name"] for r in ledger.records] == ["jit_tiny_step"]

    def test_ledger_attributes_by_name_and_site(self):
        from turboprune_tpu.analysis.compile_audit import _attribution

        spans = [("lib/engine.py", 10, 40, "entry _apply")]
        names = {"_apply", "train_step"}
        rec = {"name": "jit_train_step", "site": None}
        assert "name match" in _attribution(rec, names, spans)
        rec = {
            "name": "jit_mystery",
            "site": (str(REPO / "lib/engine.py"), 22),
        }
        assert "entry _apply" in _attribution(rec, names, spans)
        rec = {
            "name": "jit_mystery",
            "site": (str(REPO / "lib/other.py"), 5),
        }
        assert _attribution(rec, names, spans) is None
