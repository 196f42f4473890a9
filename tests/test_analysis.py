"""Tests for repro-lint (src/repro/analysis): engine, rules, CLI, reporters.

Every rule gets at least one positive (flags) and one negative (stays quiet)
fixture, driven through :func:`repro.analysis.lint_source` with an injected
module identity so scoping is exercised too.  The meta-tests at the bottom
lint the analyzer itself and the full src tree — the same contract the CI
gate enforces — and a subprocess test proves both ``repro.analysis`` and
``repro.obs.report`` import with third-party packages made unimportable.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import (
    ENGINE_RULES,
    SCHEMA_VERSION,
    all_rules,
    known_rule_names,
    lint_paths,
    lint_source,
    parse_suppressions,
    render_json,
    render_text,
)
from repro.analysis.cli import main as cli_main

SRC = Path(__file__).resolve().parent.parent / "src"


def rules_of(src: str, module: str, path: str = "<fixture>") -> list[str]:
    """Lint a fixture and return the sorted list of rule names that fired."""
    report = lint_source(textwrap.dedent(src), path=path, module=module)
    return sorted({f.rule for f in report.findings})


# ---------------------------------------------------------------- registry
def test_rule_catalog_complete():
    names = {r.name for r in all_rules()}
    assert {
        "no-eager-jax", "stdlib-only", "rng-discipline", "float-determinism",
        "spawn-spec-picklable", "merge-order", "obs-zero-overhead",
        "lock-mutation", "lock-order", "lock-blocking",
    } <= names
    assert len(names) >= 8
    for rule in all_rules():
        assert rule.description
    assert set(ENGINE_RULES) <= known_rule_names()


def test_scoping_rules_do_not_fire_off_scope():
    # An eager jax import in a jax-heavy module (repro.train) is fine.
    assert rules_of("import jax\n", module="repro.train.steps") == []


# ------------------------------------------------------------- no-eager-jax
def test_no_eager_jax_flags_module_scope_import():
    assert rules_of("import jax\n", module="repro.api.oracle") == ["no-eager-jax"]


def test_no_eager_jax_flags_transitive_heavy_module():
    src = "from repro.kernels import matmul\n"
    assert rules_of(src, module="repro.serving.server") == ["no-eager-jax"]


def test_no_eager_jax_flags_models_submodule_but_not_config():
    bad = "from repro.models import transformer\n"
    assert "no-eager-jax" in rules_of(bad, module="repro.api.campaign")
    good = "from repro.models.config import ModelConfig, InputShape\n"
    assert rules_of(good, module="repro.api.campaign") == []


def test_no_eager_jax_allows_function_scope_and_type_checking():
    src = """
    from typing import TYPE_CHECKING
    if TYPE_CHECKING:
        import jax
    def f():
        import jax.numpy as jnp
        return jnp
    """
    assert rules_of(src, module="repro.api.oracle") == []


# -------------------------------------------------------------- stdlib-only
def test_stdlib_only_flags_third_party_and_heavy_repro():
    assert rules_of("import numpy\n", module="repro.obs.metrics") == ["stdlib-only"]
    found = rules_of("import repro.core.forest\n", module="repro.analysis.engine")
    assert "stdlib-only" in found


def test_stdlib_only_allows_stdlib_and_own_scope():
    src = "import json, os, threading\nfrom repro.obs.trace import span\n"
    assert rules_of(src, module="repro.obs.report") == []


def test_stdlib_only_allows_deferred_numpy():
    src = "def f(values):\n    import numpy as np\n    return np.asarray(values)\n"
    assert rules_of(src, module="repro.obs.metrics") == []


# ----------------------------------------------------------- rng-discipline
def test_rng_flags_module_global_numpy_state():
    src = "import numpy as np\ndef f():\n    return np.random.rand(3)\n"
    assert rules_of(src, module="repro.core.prs") == ["rng-discipline"]


def test_rng_flags_unseeded_default_rng():
    src = "import numpy as np\nrng = np.random.default_rng()\n"
    assert rules_of(src, module="repro.core.prs") == ["rng-discipline"]


def test_rng_flags_data_dependent_conditional_draw():
    src = """
    def f(rng, y):
        if y.std() > 0:
            return rng.choice(10)
        return 0
    """
    assert rules_of(src, module="repro.core.forest") == ["rng-discipline"]


def test_rng_tracks_bound_method_alias():
    src = """
    def f(rng, xs):
        choice = rng.choice
        out = []
        while xs:
            out.append(choice(3))
            xs = xs[1:]
        return out
    """
    assert rules_of(src, module="repro.core.forest") == ["rng-discipline"]


def test_rng_allows_seeded_unconditional_draws():
    src = """
    import numpy as np
    def f(seed, n):
        rng = np.random.default_rng(seed)
        idx = rng.integers(0, n, size=n)
        for _ in range(3):
            idx = rng.permutation(idx)
        return idx
    """
    assert rules_of(src, module="repro.core.prs") == []


# -------------------------------------------------------- float-determinism
def test_float_det_flags_sum_over_set():
    src = "def f(xs):\n    return sum({x * 2 for x in xs})\n"
    assert rules_of(src, module="repro.core.network") == ["float-determinism"]


def test_float_det_flags_genexp_over_set_and_fsum():
    src = "import math\ndef f(s):\n    return math.fsum(v for v in s)\n"
    assert "float-determinism" in rules_of(src, module="repro.core.network")
    src2 = "def f(s):\n    return sum(v * v for v in set(s))\n"
    assert rules_of(src2, module="repro.core.network") == ["float-determinism"]


def test_float_det_flags_augassign_loop_over_set():
    src = """
    def f(s):
        total = 0.0
        for v in set(s):
            total += v
        return total
    """
    assert rules_of(src, module="repro.accelerators.base") == ["float-determinism"]


def test_float_det_allows_sorted_iteration():
    src = """
    def f(s):
        total = 0.0
        for v in sorted(set(s)):
            total += v
        return total + sum(sorted(s))
    """
    assert rules_of(src, module="repro.core.network") == []


# ---------------------------------------------------- spawn-spec-picklable
def test_spawn_spec_flags_parameterised_platform_without_override():
    src = """
    class FancySim(Platform):
        def __init__(self, freq_mhz):
            self.freq_mhz = freq_mhz
    """
    found = rules_of(src, module="repro.accelerators.fancy")
    assert found == ["spawn-spec-picklable"]


def test_spawn_spec_flags_non_literal_component():
    src = """
    class FancySim(Platform):
        def spawn_spec(self):
            return ("fancy", {"fn": lambda x: x}, __name__)
    """
    assert rules_of(src, module="repro.accelerators.fancy") == ["spawn-spec-picklable"]


def test_spawn_spec_flags_wrong_arity():
    src = """
    class FancySim(Platform):
        def spawn_spec(self):
            return ("fancy", {})
    """
    assert rules_of(src, module="repro.accelerators.fancy") == ["spawn-spec-picklable"]


def test_spawn_spec_allows_literal_spec_and_unparameterised():
    src = """
    class GoodSim(Platform):
        def __init__(self, chip="a"):
            self.chip = chip
        def spawn_spec(self):
            kwargs = {"chip": self.chip, "n": 4}
            return ("good", kwargs, "repro.accelerators.good")

    class Plain(Platform):
        pass
    """
    assert rules_of(src, module="repro.accelerators.good") == []


def test_spawn_spec_ignores_non_platform_classes():
    src = """
    class Helper:
        def __init__(self, fn):
            self.fn = fn
    """
    assert rules_of(src, module="repro.accelerators.util") == []


# --------------------------------------------------------------- merge-order
def test_merge_order_flags_as_completed_use_and_import():
    src = "from concurrent.futures import as_completed\n"
    assert rules_of(src, module="repro.runtime.scheduler") == ["merge-order"]
    src2 = """
    import concurrent.futures as cf
    def f(futs):
        return [f.result() for f in cf.as_completed(futs)]
    """
    assert "merge-order" in rules_of(src2, module="repro.runtime.scheduler")


def test_merge_order_quiet_on_indexed_merge():
    src = """
    def f(futures):
        return [futures[i].result() for i in range(len(futures))]
    """
    assert rules_of(src, module="repro.runtime.scheduler") == []


# --------------------------------------------------------- obs-zero-overhead
def test_obs_flags_fstring_name_and_dict_args():
    src = "def f(op):\n    with span(f'serve.{op}'):\n        pass\n"
    assert rules_of(src, module="repro.serving.server") == ["obs-zero-overhead"]
    src2 = "def f(n):\n    with span('x', {'n': n}):\n        pass\n"
    assert rules_of(src2, module="repro.serving.server") == ["obs-zero-overhead"]


def test_obs_allows_constant_name_and_if_sp_pattern():
    src = """
    def f(n):
        sp = span("serve.coalesce", cat="serving")
        if sp:
            sp.set(n=n)
        with sp:
            return n
    """
    assert rules_of(src, module="repro.serving.server") == []


def test_obs_allows_tracer_guarded_instant():
    src = """
    def f(label, i):
        if get_tracer() is not None:
            instant("runtime.retry", {"label": label, "chunk": i})
    """
    assert rules_of(src, module="repro.runtime.scheduler") == []


# ------------------------------------------------------------ lock rules
LOCKY = "repro.serving.fixture"


def test_lock_mutation_flags_unlocked_write_of_shared_attr():
    src = """
    import threading
    class Q:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
        def put(self, x):
            with self._lock:
                self._items.append(x)
        def reset(self):
            self._items = []
    """
    report = lint_source(textwrap.dedent(src), module=LOCKY)
    assert [f.rule for f in report.findings] == ["lock-mutation"]
    assert "reset" in report.findings[0].message


def test_lock_mutation_exempts_locked_suffix_methods():
    src = """
    import threading
    class Q:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
        def put(self, x):
            with self._lock:
                self._items.append(x)
        def _drain_locked(self):
            batch = self._items[:]
            del self._items[:]
            return batch
    """
    assert rules_of(src, module=LOCKY) == []


def test_lock_mutation_ignores_classes_without_locks():
    src = """
    class Plain:
        def __init__(self):
            self._items = []
        def put(self, x):
            self._items.append(x)
    """
    assert rules_of(src, module=LOCKY) == []


def test_lock_order_flags_abba():
    src = """
    import threading
    class D:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
        def one(self):
            with self._a:
                with self._b:
                    pass
        def two(self):
            with self._b:
                with self._a:
                    pass
    """
    report = lint_source(textwrap.dedent(src), module=LOCKY)
    assert [f.rule for f in report.findings] == ["lock-order"]


def test_lock_order_quiet_on_consistent_nesting():
    src = """
    import threading
    class D:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
        def one(self):
            with self._a, self._b:
                pass
        def two(self):
            with self._a:
                with self._b:
                    pass
    """
    assert rules_of(src, module=LOCKY) == []


def test_lock_blocking_flags_sleep_under_lock():
    src = """
    import threading, time
    class S:
        def __init__(self):
            self._lock = threading.Lock()
        def nap(self):
            with self._lock:
                time.sleep(1.0)
    """
    assert rules_of(src, module=LOCKY) == ["lock-blocking"]


def test_lock_blocking_exempts_condition_wait():
    src = """
    import threading
    class S:
        def __init__(self):
            self._cond = threading.Condition()
            self._ready = False
        def block(self):
            with self._cond:
                while not self._ready:
                    self._cond.wait(timeout=0.1)
    """
    assert rules_of(src, module=LOCKY) == []


def test_lock_blocking_checks_closures_with_empty_held_set():
    src = """
    import threading, time
    class S:
        def __init__(self):
            self._lock = threading.Lock()
        def make(self):
            with self._lock:
                def cb():
                    time.sleep(0.1)
                return cb
    """
    # The closure runs later, lock-free: sleep inside it must NOT flag.
    assert rules_of(src, module=LOCKY) == []


# ------------------------------------------------------------- suppressions
def test_suppression_silences_only_named_rule_on_line():
    src = "import jax  # repro-lint: disable=no-eager-jax -- fixture reason\n"
    report = lint_source(src, module="repro.api.oracle")
    assert report.findings == []
    assert report.suppressed == 1


def test_standalone_suppression_targets_next_code_line():
    src = (
        "# repro-lint: disable=no-eager-jax -- reason spans\n"
        "# several comment lines before the statement\n"
        "import jax\n"
    )
    report = lint_source(src, module="repro.api.oracle")
    assert report.findings == []
    assert report.suppressed == 1


def test_suppression_without_reason_is_a_finding():
    src = "import jax  # repro-lint: disable=no-eager-jax\n"
    rules = rules_of(src, module="repro.api.oracle")
    assert "bad-suppression" in rules
    assert "no-eager-jax" in rules  # and it does NOT silence the finding


def test_suppression_of_unknown_rule_is_a_finding():
    src = "x = 1  # repro-lint: disable=no-such-rule -- whatever\n"
    assert rules_of(src, module="repro.api.oracle") == ["bad-suppression"]


def test_marker_inside_string_literal_is_ignored():
    src = 'DOC = "# repro-lint: disable=no-eager-jax"\n'
    report = lint_source(src, module="repro.api.oracle")
    assert report.findings == [] and report.suppressed == 0


def test_parse_suppressions_multi_rule():
    src = "x = 1  # repro-lint: disable=merge-order,no-eager-jax -- both\n"
    by_line, malformed = parse_suppressions(src, known_rule_names())
    assert malformed == []
    assert by_line[1][0].rules == ("merge-order", "no-eager-jax")
    assert by_line[1][0].reason == "both"


def test_parse_error_is_reported_not_raised():
    report = lint_source("def broken(:\n", module="repro.core.x")
    assert [f.rule for f in report.findings] == ["parse-error"]


# ---------------------------------------------------------------- reporters
def test_json_reporter_schema():
    result = lint_paths([str(SRC / "repro" / "analysis")])
    payload = json.loads(render_json(result))
    assert payload["schema_version"] == SCHEMA_VERSION
    assert set(payload) == {
        "schema_version", "files", "findings", "counts", "suppressed",
        "elapsed_s", "rule_seconds",
    }
    assert payload["files"] >= 6
    for f in payload["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "message", "module"}
    assert all(isinstance(v, float) for v in payload["rule_seconds"].values())


def test_text_reporter_mentions_counts():
    report = lint_source("import jax\n", module="repro.api.x", path="x.py")
    from repro.analysis.engine import LintResult

    result = LintResult(
        findings=report.findings, files=1, suppressed=0, elapsed_s=0.01,
        rule_seconds={},
    )
    text = render_text(result, statistics=True)
    assert "x.py:1:0: no-eager-jax:" in text
    assert "1 finding(s)" in text


# ---------------------------------------------------------------------- CLI
def test_cli_exit_codes_and_json(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    dirty = tmp_path / "repro" / "api"
    dirty.mkdir(parents=True)
    (dirty / "bad.py").write_text("import jax\n")
    assert cli_main([str(clean)]) == 0
    capsys.readouterr()
    assert cli_main([str(dirty), "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"] == {"no-eager-jax": 1}


def test_cli_select_and_ignore(tmp_path):
    pkg = tmp_path / "repro" / "api"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text("import jax\n")
    assert cli_main([str(pkg), "--select", "merge-order"]) == 0
    assert cli_main([str(pkg), "--ignore", "no-eager-jax"]) == 0
    assert cli_main([str(pkg), "--select", "no-eager-jax"]) == 1


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "no-eager-jax" in out and "lock-blocking" in out


def test_cli_rejects_unknown_rule_names():
    with pytest.raises(SystemExit):
        cli_main(["--select", "bogus-rule", "src"])


# --------------------------------------------------------------- meta-tests
def test_meta_lint_analysis_package_is_clean():
    """The analyzer must pass its own rules (including stdlib-only)."""
    result = lint_paths([str(SRC / "repro" / "analysis")])
    assert result.findings == []


def test_full_tree_lint_is_clean_and_fast():
    """The CI-gate contract: src/ lints clean with reasoned suppressions."""
    result = lint_paths([str(SRC)])
    assert result.findings == []
    assert result.files >= 90
    assert result.suppressed >= 5  # the documented deliberate exceptions


def test_full_tree_lint_is_deterministic():
    """Two lints of one tree give the same findings, suppressions and files."""
    runs = [lint_paths([str(SRC)]) for _ in range(2)]
    prints = {
        (tuple(f.sort_key() for f in r.findings), r.suppressed, r.files) for r in runs
    }
    assert len(prints) == 1


def test_analysis_and_obs_report_import_without_third_party():
    """Satellite contract: bare-Python importability, enforced dynamically.

    A meta_path blocker makes numpy/jax/scipy/pandas unimportable, then
    imports repro.analysis + repro.obs.report and runs a real lint — proving
    the stdlib-only rule's subject matter, not just its syntax.
    """
    code = textwrap.dedent(
        """
        import sys
        BLOCKED = {"numpy", "jax", "jaxlib", "scipy", "sklearn", "pandas"}
        class Blocker:
            def find_module(self, name, path=None):
                return self if name.split(".")[0] in BLOCKED else None
            def find_spec(self, name, path=None, target=None):
                if name.split(".")[0] in BLOCKED:
                    raise ImportError(f"{name} blocked by test")
                return None
        sys.meta_path.insert(0, Blocker())
        import repro.analysis
        import repro.obs
        import repro.obs.report
        import repro.obs.metrics
        import repro.obs.trace
        report = repro.analysis.lint_source("import jax\\n", module="repro.api.x")
        assert [f.rule for f in report.findings] == ["no-eager-jax"], report
        print("ok")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"
