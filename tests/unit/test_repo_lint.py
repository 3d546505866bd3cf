"""Repo-lint tests: the AST pass catches each planted JAX pitfall, the
traced-set discovery has the right reach, and — the tier-1 gate — the live
``deepspeed_tpu/`` package is clean (un-allowlisted findings == 0)."""

import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "tools"))

import repo_lint  # noqa: E402
from repo_lint import PACKAGE, lint_paths  # noqa: E402


def _lint_source(tmp_path, src):
    (tmp_path / "mod.py").write_text(textwrap.dedent(src))
    findings, traced = lint_paths(str(tmp_path))
    return findings, traced


def test_detects_each_pitfall_inside_jitted_fn(tmp_path):
    findings, _ = _lint_source(tmp_path, """
        import time, datetime
        import numpy as np
        import jax

        def step(p):
            t = time.time()                      # frozen timestamp
            n = np.random.randn(3)               # frozen randomness
            d = datetime.datetime.now()          # frozen timestamp
            v = p.sum().item()                   # concretization
            return v + t + n[0]

        step_c = jax.jit(step)
        """)
    pats = sorted(f["pattern"] for f in findings)
    assert pats == [".item()", "datetime.datetime.now", "np.random.randn",
                    "time.time"]
    assert all(f["function"] == "step" for f in findings)
    assert all(not f["allowed"] for f in findings)


def test_traced_reach_decorator_nested_and_transitive(tmp_path):
    findings, traced = _lint_source(tmp_path, """
        import time
        import numpy as np
        import jax

        @jax.jit
        def decorated(x):
            def inner(y):                 # nested def traces with parent
                return y * np.random.rand()
            return inner(x)

        def helper(x):                    # traced transitively via body
            return x + time.time()

        def body(carry, x):
            return helper(carry), x

        out = jax.lax.scan(body, 0.0, None)

        def host_only(x):                 # never traced: no finding
            return time.time() + np.random.rand()
        """)
    by_fn = {f["function"]: f["pattern"] for f in findings}
    assert by_fn == {"decorated.inner": "np.random.rand",
                     "helper": "time.time"}
    mod_traced = traced[os.path.join(
        os.path.relpath(str(tmp_path), repo_lint.REPO), "mod.py")]
    assert "host_only" not in mod_traced
    assert {"decorated", "decorated.inner", "body", "helper"} <= \
        set(mod_traced)


def test_allowlist_suppresses_with_reason(tmp_path, monkeypatch):
    src = """
        import numpy as np
        import jax

        def step(p):
            return p * np.random.rand()

        step_c = jax.jit(step)
        """
    findings, _ = _lint_source(tmp_path, src)
    assert len(findings) == 1 and not findings[0]["allowed"]
    rel = findings[0]["file"]
    monkeypatch.setitem(repo_lint.ALLOWLIST, f"{rel}:step",
                        "fixture: intentionally planted")
    findings, _ = _lint_source(tmp_path, src)
    assert findings[0]["allowed"]
    assert findings[0]["allow_reason"] == "fixture: intentionally planted"


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    findings, _ = lint_paths(str(tmp_path))
    assert len(findings) == 1 and findings[0]["pattern"] == "syntax-error"


def test_package_is_clean():
    """The tier-1 gate: no JAX pitfalls inside traced code in
    deepspeed_tpu/ (time.time/np.random/.item()/datetime.now would bake
    trace-time values into compiled programs). New intentional sites get an
    ALLOWLIST entry in tools/repo_lint.py with a reason."""
    findings, traced = lint_paths(PACKAGE)
    bad = [f for f in findings if not f["allowed"]]
    assert not bad, bad
    # the traced-set discovery is actually finding the hot programs, not
    # silently matching nothing
    assert sum(len(v) for v in traced.values()) > 50


def _sources(*dirs):
    for d in dirs:
        for root, _, files in os.walk(os.path.join(repo_lint.REPO, d)):
            for name in files:
                if name.endswith(".py"):
                    path = os.path.join(root, name)
                    with open(path) as f:
                        yield os.path.relpath(path, repo_lint.REPO), f.read()


@pytest.mark.parametrize("what,pattern", [
    ("an attribute .paged (an engine asking which KV store it has)",
     r"\.paged\b"),
    ("kv_pool.enabled", r"kv_pool\.enabled|kv_pool\[.enabled.\]"),
    ("a --paged flag", r"--paged\b|args\.paged\b"),
    ("attention_backend as anything but the snapshot's key",
     r"\.attention_backend\b|--attention-backend|\battention_backend\s*="),
])
def test_one_kv_store(what, pattern):
    """PR 31's fence: the serving engine has ONE KV store, the paged pool.
    Nothing in the package or the tools asks which pool an engine has,
    selects one, or selects a decode attention by name; ``attention_backend``
    survives only as the key of ``snapshot()["kv_pool"]`` (and of the tools'
    artifacts) that names the path that ran."""
    import re

    hits = [f"{rel}:{i}: {line.strip()}"
            for rel, src in _sources("deepspeed_tpu", "tools")
            for i, line in enumerate(src.splitlines(), 1)
            if re.search(pattern, line)]
    assert not hits, (what, hits)
    from deepspeed_tpu.config.config import KVPoolConfig

    assert not {"enabled", "attention_backend"} & set(KVPoolConfig().to_dict())
