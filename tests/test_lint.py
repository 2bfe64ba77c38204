"""The static spec linter (`consensus_specs_tpu/lint.py`): catches
undefined names, unknown config attributes and call-arity drift, gives
lambdas their own scope, stays quiet on the real spec tree."""

import ast
import builtins

from consensus_specs_tpu.lint import (
    _call_arity_findings,
    _function_findings,
    lint_spec,
)


def _findings(src, known=frozenset(), config_keys=frozenset()):
    tree = ast.parse(src)
    out = []
    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            out.extend(_function_findings(
                node,
                set(known) | {"config"} | set(vars(builtins)),
                set(config_keys), "x.py"))
    return out


def test_catches_undefined_helper_call():
    # the advisor's round-4 bug class: a helper name no fork defines
    src = ("def f(state, block):\n"
           "    return compute_timestamp_at_slot(state, block.slot)\n")
    found = _findings(src, known={"compute_time_at_slot"})
    assert len(found) == 1
    assert "compute_timestamp_at_slot" in found[0]


def test_accepts_known_and_local_names():
    src = ("def f(state):\n"
           "    x = helper(state)\n"
           "    items = [y for y in x]\n"
           "    with open('f') as fh:\n"
           "        pass\n"
           "    try:\n"
           "        pass\n"
           "    except ValueError as err:\n"
           "        return err\n"
           "    return items\n")
    assert _findings(src, known={"helper"}) == []


def test_nested_closure_uses_enclosing_scope():
    src = ("def outer(state, body):\n"
           "    def for_ops(operations, fn):\n"
           "        for operation in operations:\n"
           "            fn(state, operation)\n"
           "    for_ops(body.deposits, process_deposit)\n")
    assert _findings(src, known={"process_deposit"}) == []


def test_catches_unknown_config_attribute():
    src = ("def f(epoch):\n"
           "    return config.NO_SUCH_KNOB + epoch\n")
    found = _findings(src, config_keys={"REAL_KNOB"})
    assert len(found) == 1
    assert "config.NO_SUCH_KNOB" in found[0]


def test_lambda_params_do_not_leak_into_enclosing_scope():
    # regression: lambda params used to join the enclosing bound set,
    # masking genuine undefined names AFTER the lambda
    src = ("def f(xs):\n"
           "    g = lambda n: n + 1\n"
           "    return n\n")
    found = _findings(src)
    assert len(found) == 1
    assert "undefined name 'n'" in found[0]
    assert ":3:" in found[0]


def test_lambda_body_sees_own_params_and_enclosing_locals():
    src = ("def f(xs, offset):\n"
           "    g = lambda n: n + offset\n"
           "    return g(xs)\n")
    assert _findings(src) == []


def test_lambda_body_undefined_name_is_caught():
    src = ("def f(xs):\n"
           "    return sorted(xs, key=lambda v: weight(v))\n")
    found = _findings(src)
    assert len(found) == 1
    assert "undefined name 'weight'" in found[0]


def test_nested_lambda_chain_scopes():
    src = ("def f(xs):\n"
           "    add = lambda a: lambda b: a + b\n"
           "    return add(1)(2)\n")
    assert _findings(src) == []


def test_lambda_default_evaluates_in_enclosing_scope():
    src = ("def f(xs):\n"
           "    g = lambda n=missing: n\n"
           "    return g()\n")
    found = _findings(src)
    assert len(found) == 1
    assert "undefined name 'missing'" in found[0]


# --- call arity --------------------------------------------------------------


def _arity(src, helpers):
    tree = ast.parse(src)
    out = []
    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            out.extend(_call_arity_findings(node, helpers, {}, "x.py"))
    return out


def _helper2(state, epoch):
    return state


def test_arity_drift_is_caught():
    src = ("def f(state):\n"
           "    return get_thing(state)\n")
    found = _arity(src, {"get_thing": _helper2})
    assert len(found) == 1
    assert "get_thing()" in found[0] and ":2:" in found[0]


def test_matching_call_and_keywords_pass():
    src = ("def f(state):\n"
           "    return get_thing(state, epoch=3)\n")
    assert _arity(src, {"get_thing": _helper2}) == []


def test_unknown_keyword_is_caught():
    src = ("def f(state):\n"
           "    return get_thing(state, slot=3)\n")
    assert len(_arity(src, {"get_thing": _helper2})) == 1


def test_starargs_and_shadowed_names_are_skipped():
    src = ("def f(state, args):\n"
           "    get_thing = state.fn\n"
           "    get_thing(1, 2, 3)\n"
           "    return helper(*args)\n")
    assert _arity(src, {"get_thing": _helper2,
                        "helper": _helper2}) == []


def test_real_spec_tree_is_clean_minimal_phase0():
    assert lint_spec("phase0", "minimal") == []


def test_real_spec_tree_is_clean_minimal_electra():
    assert lint_spec("electra", "minimal") == []


# --- env-knob discipline ---------------------------------------------------


def _knob_repo(tmp_path, readme: str, code: str):
    (tmp_path / "README.md").write_text(readme)
    (tmp_path / "mod.py").write_text(code)
    return tmp_path


def test_serve_knob_needs_serving_section_mention(tmp_path):
    from consensus_specs_tpu.lint import lint_env_knobs

    readme = ("## Serving\n\nno knob mention here\n\n"
              "## Environment knobs\n\n"
              "| `CST_SERVE_FOO` | unset | a knob |\n")
    # knob name assembled at runtime so THIS file's source never
    # pattern-matches the tree-wide env-read scan
    knob = "CST_" + "SERVE_FOO"
    repo = _knob_repo(tmp_path, readme,
                      "import os\nX = os.environ.get(%r)\n" % knob)
    found = lint_env_knobs(repo)
    assert len(found) == 1
    assert "Serving" in found[0] and knob in found[0]


def test_serve_knob_with_section_mention_passes(tmp_path):
    from consensus_specs_tpu.lint import lint_env_knobs

    readme = ("## Serving\n\nthe `CST_SERVE_FOO` knob tunes it\n\n"
              "## Environment knobs\n\n"
              "| `CST_SERVE_FOO` | unset | a knob |\n")
    knob = "CST_" + "SERVE_FOO"
    repo = _knob_repo(tmp_path, readme,
                      "import os\nX = os.environ.get(%r)\n" % knob)
    assert lint_env_knobs(repo) == []


def test_merkle_knob_needs_incremental_section_mention(tmp_path):
    from consensus_specs_tpu.lint import lint_env_knobs

    readme = ("## Incremental merkleization\n\nno knob mention here\n\n"
              "## Environment knobs\n\n"
              "| `CST_MERKLE_FOO` | unset | a knob |\n")
    knob = "CST_" + "MERKLE_FOO"
    repo = _knob_repo(tmp_path, readme,
                      "import os\nX = os.environ.get(%r)\n" % knob)
    found = lint_env_knobs(repo)
    assert len(found) == 1
    assert "Incremental merkleization" in found[0] and knob in found[0]


def test_merkle_knob_with_section_mention_passes(tmp_path):
    from consensus_specs_tpu.lint import lint_env_knobs

    readme = ("## Incremental merkleization\n\nsweep via "
              "`CST_MERKLE_FOO=0.01,1.0` records\n\n"
              "## Environment knobs\n\n"
              "| `CST_MERKLE_FOO` | unset | a knob |\n")
    knob = "CST_" + "MERKLE_FOO"
    repo = _knob_repo(tmp_path, readme,
                      "import os\nX = os.environ.get(%r)\n" % knob)
    assert lint_env_knobs(repo) == []


def test_undocumented_knob_still_caught(tmp_path):
    from consensus_specs_tpu.lint import lint_env_knobs

    knob = "CST_" + "NEW_KNOB"
    repo = _knob_repo(tmp_path, "## Serving\n",
                      "import os\nX = os.environ[%r]\n" % knob)
    found = lint_env_knobs(repo)
    assert len(found) == 1 and knob in found[0]


def test_real_tree_knob_table_in_sync():
    from consensus_specs_tpu.lint import lint_env_knobs

    assert lint_env_knobs() == []
