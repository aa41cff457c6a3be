"""keystone-lint rules (lint/rules.py) on fixture snippets, plus the
shipped-tree cleanliness gate CI relies on."""

import os
import textwrap

import pytest

from keystone_tpu.lint import (
    LINT_CODES,
    Finding,
    LintContext,
    build_context,
    lint_paths,
    lint_source,
)

CTX = LintContext(
    metric_names={"keystone_good_total"},
    probe_sites={"serving.apply"},
)


def run(src, path="pkg/mod.py", ctx=CTX):
    return lint_source(textwrap.dedent(src), path=path, context=ctx)


def codes(src, path="pkg/mod.py", ctx=CTX):
    return [f.rule for f in run(src, path, ctx)]


# ------------------------------------------------------------------- KV501


def test_env_read_flagged():
    assert codes("import os\nx = os.environ.get('KEYSTONE_FOO')\n") == ["KV501"]
    assert codes("import os\nx = os.getenv('KEYSTONE_FOO')\n") == ["KV501"]
    assert codes("import os\nx = os.environ['KEYSTONE_FOO']\n") == ["KV501"]
    assert codes("import os\nok = 'X' in os.environ\n") == ["KV501"]
    assert codes("import os\nenv = dict(os.environ)\n") == ["KV501"]


def test_env_write_allowed():
    assert codes("import os\nos.environ['X'] = 'y'\n") == []
    assert codes("import os\nos.environ.pop('X', None)\n") == []
    assert codes("import os\nos.environ.update({'X': 'y'})\n") == []


def test_env_pragma_same_line_and_above():
    assert codes(
        "import os\nenv = dict(os.environ)  # keystone: allow-env\n"
    ) == []
    assert codes(
        """\
        import os
        # child env is a structural clone  # keystone: allow-env
        env = dict(os.environ)
        """
    ) == []


def test_env_rule_skips_envknobs_module():
    src = "import os\nx = os.environ.get('K')\n"
    assert codes(src, path="keystone_tpu/envknobs.py") == []
    assert codes(src, path="keystone_tpu/other.py") == ["KV501"]


# ------------------------------------------------------------------- KV502

HOT = os.path.join("keystone_tpu", "serving", "server.py")


def test_sync_flagged_only_in_hot_modules():
    src = "import jax\njax.block_until_ready(x)\n"
    assert codes(src, path=HOT) == ["KV502"]
    assert codes(src, path="keystone_tpu/ops/learning/zca.py") == []


def test_sync_variants_flagged():
    assert codes("v = x.item()\n", path=HOT) == ["KV502"]
    assert codes("import numpy as np\nv = np.asarray(x)\n", path=HOT) == [
        "KV502"
    ]
    # .item(i) (indexed) and non-numpy asarray are not the sync idiom
    assert codes("v = x.item(3)\n", path=HOT) == []
    assert codes("v = obj.asarray(x)\n", path=HOT) == []


def test_sync_under_sync_gate_allowed():
    assert codes(
        """\
        def timed(sync):
            if sync:
                x.block_until_ready()
        """,
        path=HOT,
    ) == []
    assert codes(
        """\
        def force_sync(value):
            value.block_until_ready()
        """,
        path=HOT,
    ) == []


def test_sync_pragma_allowed():
    assert codes(
        "x.block_until_ready()  # completion barrier  # keystone: allow-sync\n",
        path=HOT,
    ) == []


# ------------------------------------------------------------------- KV503


def test_undeclared_metric_name_flagged():
    assert codes("m = metric('keystone_bad_total')\n") == ["KV503"]
    assert codes("m = metric('keystone_good_total')\n") == []


def test_metric_shape_excludes_package_paths_and_docstrings():
    assert codes("import_module('keystone_tpu.data.dataset')\n") == []
    assert codes("x = 'keystone_tpu'\n") == []
    assert codes('"""mentions keystone_bad_total in a docstring"""\n') == []
    # no schema context → rule disabled, not a false positive storm
    assert codes("m = metric('keystone_bad_total')\n", ctx=LintContext()) == []


# ------------------------------------------------------------------- KV504


def test_unregistered_probe_site_flagged():
    assert codes("probe('serving.apply')\n") == []
    assert codes("probe('serving.unknown')\n") == ["KV504"]


def test_probe_site_resolved_through_module_constant():
    assert codes(
        "SITE = 'serving.unknown'\ndef f():\n    probe(SITE)\n"
    ) == ["KV504"]
    assert codes(
        "SITE = 'serving.apply'\ndef f():\n    probe(SITE)\n"
    ) == []
    # unresolvable labels are skipped, not guessed at
    assert codes("def f(site):\n    probe(site)\n") == []


# ------------------------------------------------------------------- KV505


def test_donation_requires_ownership_annotation():
    assert codes(
        "import jax\nf = jax.jit(g, donate_argnums=(0,))\n"
    ) == ["KV505"]
    assert codes(
        """\
        import jax
        # carry is loop-owned  # keystone: owns-donated
        f = jax.jit(g, donate_argnums=(0,))
        """
    ) == []
    # an unconditionally empty tuple donates nothing
    assert codes(
        "import jax\nf = jax.jit(g, donate_argnums=())\n"
    ) == []
    # a conditional donation still donates on one branch
    assert codes(
        "import jax\nf = jax.jit(g, donate_argnums=(0,) if d else ())\n"
    ) == ["KV505"]


# ------------------------------------------------------------------ driver


def test_syntax_error_reported_not_raised():
    findings = run("def broken(:\n")
    assert [f.rule for f in findings] == ["KV500"]


def test_finding_render_and_json():
    f = Finding("KV501", "a.py", 3, "msg")
    assert f.render() == "a.py:3: KV501 msg"
    assert f.to_json() == {
        "rule": "KV501", "path": "a.py", "line": 3, "message": "msg",
    }


def test_lint_codes_table():
    assert set(LINT_CODES) == {
        "KV501", "KV502", "KV503", "KV504", "KV505", "KV506",
    }


def test_build_context_reads_real_registries():
    import keystone_tpu

    root = os.path.dirname(keystone_tpu.__file__)
    ctx = build_context(root)
    assert "keystone_verify_runs_total" in ctx.metric_names
    assert "serving.apply" in ctx.probe_sites


def test_shipped_tree_is_clean():
    """The CI gate: keystone-lint over the shipped package finds
    nothing. A new finding means either fix the code or annotate the
    reviewed exception — never ignore."""
    import keystone_tpu

    root = os.path.dirname(keystone_tpu.__file__)
    findings = lint_paths([root])
    assert findings == [], "\n".join(f.render() for f in findings)


# ------------------------------------------- pinned true-positive fixes


def test_group_batch_reads_metadata_without_host_sync():
    """KV502 true positive fixed: batch grouping used np.asarray on every
    payload leaf — a synchronous device→host copy per request — just to
    read the shape. It must use leaf metadata."""
    from keystone_tpu.serving.config import Request
    from keystone_tpu.serving.server import PipelineServer

    class DeviceLeaf:
        shape = (4,)
        dtype = "float32"

        def __array__(self, *a, **k):  # pragma: no cover - must not run
            raise AssertionError("host sync on the grouping path")

    reqs = [Request(payload=DeviceLeaf(), model="m") for _ in range(3)]
    groups = PipelineServer._group_batch(reqs)
    assert len(groups) == 1 and len(groups[0]) == 3


# ------------------------------------------------------------------- KV506


def test_cost_analysis_outside_home_flagged():
    src = """
    def harvest(compiled):
        return compiled.cost_analysis()
    """
    assert codes(src) == ["KV506"]
    # bare-name calls count too
    assert codes("x = cost_analysis()\n") == ["KV506"]


def test_cost_analysis_in_obs_cost_allowed():
    src = "facts = lowered.cost_analysis()\n"
    assert codes(src, path=os.path.join("pkg", "obs", "cost.py")) == []


def test_cost_analysis_mention_without_call_ok():
    # docstrings/comments/attribute references don't flag — only calls
    src = '"""uses cost_analysis() downstream"""\nname = "cost_analysis"\n'
    assert codes(src) == []


def test_kv506_registered():
    assert "KV506" in LINT_CODES
