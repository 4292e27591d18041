"""The repo-specific AST linter (acg_tpu/analysis/astlint.py): every
rule fires on its inline counter-example, stays quiet on the blessed
idioms, honors ``# acg: allow-<rule>`` pragmas — and the tree itself is
clean (the PR 9 satellite: true violations fixed, deliberate gather
sites pragma'd)."""

import os

from acg_tpu.analysis.astlint import RULES, lint_source, lint_tree

HOT = "acg_tpu/ops/example.py"       # a hot-module path for the rules
COLD = "acg_tpu/partition/example.py"  # not in ops/solvers/parallel?
# NOTE: partition/ is not a hot subpackage; see _HOT_PARTS


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# E1: ellipsis subscripts


def test_e1_ellipsis_slice_with_bounds_fires():
    assert _rules(lint_source("y = x[..., a:b]\n", HOT)) == ["gather"]
    assert _rules(lint_source("y = x[..., :n]\n", HOT)) == ["gather"]
    assert _rules(lint_source("y = x[..., 3:]\n", HOT)) == ["gather"]


def test_e1_advanced_index_fires():
    assert _rules(lint_source("y = x[..., colidx]\n", HOT)) == ["gather"]
    assert _rules(lint_source("y = x[..., jnp.clip(i, 0, None)]\n",
                              HOT)) == ["gather"]
    assert _rules(lint_source("y = x[..., idx[r]]\n", HOT)) == ["gather"]


def test_e1_blessed_idioms_stay_quiet():
    for src in ("y = x[..., None]\n",          # expand_dims
                "y = x[..., 0]\n",             # static literal
                "y = x[..., -1]\n",
                "y = x[..., j]\n",             # unrolled loop counter
                "y = x[..., s + 1, s + 1]\n",  # static arithmetic
                "y = x[..., :]\n",             # full slice
                "y = x[..., :, None]\n",
                "y = np.asarray(x)[..., a:b]\n",   # host NumPy
                "d.at[..., 1:].add(v)\n",      # .at update idiom
                "x[..., :n] = v\n"):           # store, not load
        assert lint_source(src, HOT) == [], src


def test_e1_only_in_hot_modules():
    src = "y = x[..., a:b]\n"
    assert lint_source(src, "acg_tpu/io/mtxfile.py") == []
    assert lint_source(src, "acg_tpu/solvers/x.py") != []
    assert lint_source(src, "acg_tpu/parallel/x.py") != []


# ---------------------------------------------------------------------------
# E2: collectives without an explicit axis


def test_e2_axis_name_required():
    assert _rules(lint_source("jax.lax.psum(x)\n", HOT)) == ["axis-name"]
    assert _rules(lint_source("lax.ppermute(x)\n", HOT)) == ["axis-name"]
    assert lint_source("jax.lax.psum(x, AXIS)\n", HOT) == []
    assert lint_source("jax.lax.psum(x, axis_name=AXIS)\n", HOT) == []
    assert lint_source("jax.lax.all_gather(x, axis)\n", HOT) == []
    # unrelated names that merely contain a collective substring pass
    assert lint_source("halo_ppermute(x)\n", HOT) == []


def test_e2_applies_everywhere():
    assert _rules(lint_source("jax.lax.psum(x)\n",
                              "acg_tpu/utils/profile.py")) == ["axis-name"]


# ---------------------------------------------------------------------------
# E3: Python branches/casts on traced loop-carry values


_BODY_IF = """\
def body(carry):
    k, x = carry
    if carry[0] > 3:
        x = x + 1
    return (k, x)
"""

_BODY_FLOAT = """\
def body(carry):
    v = float(carry[1])
    return carry
"""


def test_e3_fires_inside_loop_body_functions():
    assert _rules(lint_source(_BODY_IF, HOT)) == ["traced-branch"]
    assert _rules(lint_source(_BODY_FLOAT, HOT)) == ["traced-branch"]


def test_e3_static_branches_and_host_code_pass():
    # closure flags (not parameters) are static at trace time
    ok = ("def body(carry):\n"
          "    if track_diff:\n"
          "        carry = carry\n"
          "    return carry\n")
    assert lint_source(ok, HOT) == []
    # same code outside a body/cond function: plain host Python
    host = ("def finish(res):\n"
            "    if res > 3:\n"
            "        return float(res)\n")
    assert lint_source(host, HOT) == []
    # and outside hot modules the rule does not apply
    assert lint_source(_BODY_IF, "acg_tpu/io/x.py") == []


# ---------------------------------------------------------------------------
# E4: jax.debug outside the monitor path


def test_e4_jax_debug_flagged_outside_monitor():
    src = "jax.debug.callback(f, x)\n"
    assert _rules(lint_source(src, HOT)) == ["debug-callback"]
    assert _rules(lint_source("jax.debug.print('{x}', x=x)\n",
                              HOT)) == ["debug-callback"]
    # the throttled monitor tier itself is the blessed location
    assert lint_source(src, "acg_tpu/obs/monitor.py") == []


# ---------------------------------------------------------------------------
# E5: kernel names


def test_e5_pallas_call_needs_a_name():
    src = "y = pallas_call(kern, out_shape=s)(x)\n"
    assert _rules(lint_source(src, HOT)) == ["kernel-name"]
    assert _rules(lint_source("pl.pallas_call(kern, grid=g)\n",
                              COLD)) == ["kernel-name"]
    assert lint_source("pallas_call(kern, name='acg_k', out_shape=s)\n",
                       HOT) == []


# ---------------------------------------------------------------------------
# pragmas


def test_pragma_suppresses_same_line_and_next_line():
    src = "y = x[..., colidx]  # acg: allow-gather\n"
    assert lint_source(src, HOT) == []
    src = "# acg: allow-gather\ny = x[..., colidx]\n"
    assert lint_source(src, HOT) == []
    # a pragma for a DIFFERENT rule does not suppress
    src = "y = x[..., colidx]  # acg: allow-debug-callback\n"
    assert _rules(lint_source(src, HOT)) == ["gather"]


def test_pragma_does_not_leak_past_one_line():
    src = "# acg: allow-gather\npass\ny = x[..., colidx]\n"
    assert _rules(lint_source(src, HOT)) == ["gather"]


# ---------------------------------------------------------------------------
# the tree itself


def test_source_tree_is_clean():
    """The satellite acceptance: acg_tpu/ lints clean with the
    deliberate exceptions pragma'd (halo pack gathers, ELL-tier gather,
    the distributed monitor gate)."""
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "acg_tpu")
    findings = lint_tree(root)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_deliberate_sites_carry_pragmas():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel, rule in (("acg_tpu/parallel/halo.py", "allow-gather"),
                      ("acg_tpu/ops/spmv.py", "allow-gather"),
                      ("acg_tpu/solvers/cg_dist.py",
                       "allow-debug-callback")):
        with open(os.path.join(root, rel)) as fh:
            assert f"# acg: {rule}" in fh.read(), rel


def test_lint_script_runs_clean():
    from scripts.lint_source import main as lint_main

    assert lint_main(["-q"]) == 0
    assert lint_main(["--list-rules"]) == 0


def test_syntax_error_is_reported_not_raised():
    fs = lint_source("def broken(:\n", HOT)
    assert len(fs) == 1 and fs[0].rule == "syntax"


def test_rule_catalog_matches_finding_slugs():
    assert set(RULES) == {"gather", "axis-name", "traced-branch",
                          "debug-callback", "kernel-name"}
