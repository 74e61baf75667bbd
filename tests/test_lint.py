"""The jit-safety lint is part of tier-1: the repo must stay clean
relative to the suppression baseline, and each rule must actually fire
on a seeded bad pattern."""

import textwrap

from trino_tpu.lint import (
    compare_to_baseline,
    lint_paths,
    load_baseline,
    main,
)


def _lint_source(tmp_path, source: str):
    mod = tmp_path / "seeded.py"
    mod.write_text(textwrap.dedent(source))
    return lint_paths([mod])


def _rules(violations):
    return {v.rule for v in violations}


def test_repo_is_clean_against_baseline():
    """CI gate: the whole package, new violations only."""
    violations = lint_paths(["trino_tpu"])
    new, _stale = compare_to_baseline(violations, load_baseline())
    assert not new, "new jit-safety violations:\n" + "\n".join(
        v.render() for v in new
    )


def test_cli_exit_codes(tmp_path):
    assert main(["trino_tpu"]) == 0
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    return float(jnp.sum(x))\n"
    )
    assert main([str(bad)]) != 0


def test_host_roundtrip_item(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(x):
            return x.sum().item()
        """,
    )
    assert "JIT001" in _rules(vs)


def test_host_cast_on_jnp(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(x):
            return int(jnp.max(x))
        """,
    )
    assert "JIT002" in _rules(vs)


def test_branch_on_traced_value(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(x):
            if jnp.any(x > 0):
                return x
            return -x
        """,
    )
    assert "JIT003" in _rules(vs)


def test_branch_on_static_dtype_predicate_is_fine(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(x):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x
            return x.astype(jnp.float32)
        """,
    )
    assert "JIT003" not in _rules(vs)


def test_float_literal_widening(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f():
            return jnp.array([0.5, 1.5])
        """,
    )
    assert "JIT004" in _rules(vs)


def test_float_literal_with_dtype_is_fine(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f():
            return jnp.array([0.5, 1.5], dtype=jnp.float32)
        """,
    )
    assert "JIT004" not in _rules(vs)


def test_set_iteration_order(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(parts):
            return jnp.concatenate([parts[k] for k in set(parts)])
        """,
    )
    assert "JIT005" in _rules(vs)


def test_sorted_set_iteration_is_fine(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(parts):
            return jnp.concatenate([parts[k] for k in sorted(set(parts))])
        """,
    )
    assert "JIT005" not in _rules(vs)


def test_np_compute_in_jnp_function(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import numpy as np
        import jax.numpy as jnp
        def f(x):
            y = jnp.cumsum(x)
            return np.argsort(y)
        """,
    )
    assert "JIT006" in _rules(vs)


def test_np_in_pure_host_function_is_fine(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import numpy as np
        def f(x):
            return np.argsort(x)
        """,
    )
    assert "JIT006" not in _rules(vs)


def test_host_pull_between_fragment_dispatches(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        def drive(executor, frag_a, frag_b, inputs, layouts):
            a = executor.run_fragment_program(frag_a, inputs, layouts)
            rows = a.batch.to_host()  # dead under fusion: boundary is in-jit
            return executor.run_fragment_program(frag_b, {"remote": rows}, layouts)
        """,
    )
    assert "JIT007" in _rules(vs)


def test_host_pull_after_last_dispatch_is_fine(tmp_path):
    # pulling the ROOT result after the final dispatch is the normal
    # materialization step, not an inter-fragment sync
    vs = _lint_source(
        tmp_path,
        """
        def drive(executor, frag, inputs, layouts):
            res = executor.run_fused_program([frag], inputs, layouts)
            return res.batch.to_host()
        """,
    )
    assert "JIT007" not in _rules(vs)


def test_host_pull_in_nested_scope_is_fine(tmp_path):
    # the driver-loop shape: dispatches live in a nested def, the packed
    # root pull in the parent — separate scopes, no violation
    vs = _lint_source(
        tmp_path,
        """
        def drive(executor, units, inputs, layouts):
            results = {}
            def run_units():
                for u in units:
                    results[u.id] = executor.run_fragment_program(u, inputs, layouts)
            run_units()
            root = results[max(results)]
            final = root.batch.to_host()
            run_units()
            return final
        """,
    )
    assert "JIT007" not in _rules(vs)


def test_batch_demux_pull_is_allowlisted(tmp_path):
    # the batch demultiplexer interleaves a packed pull with further
    # dispatches BY DESIGN (one D2H fans results out to K members) —
    # the exact same shape under any other name is still a violation
    src = """
        def {name}(executor, frag_a, frag_b, inputs, layouts):
            a = executor.run_fragment_program_batched(frag_a, inputs, layouts)
            rows = a.batch.to_host()
            return executor.run_fragment_program_batched(frag_b, {{"remote": rows}}, layouts)
        """
    flagged = _lint_source(tmp_path, src.format(name="drive_batch"))
    assert "JIT007" in _rules(flagged)
    allowed = _lint_source(tmp_path, src.format(name="_demux_batch_to_host"))
    assert "JIT007" not in _rules(allowed)


def test_inline_suppression_comment(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(x):
            return x.sum().item()  # lint: ignore[JIT001]
        """,
    )
    assert "JIT001" not in _rules(vs)


def test_baseline_comparison_counts(tmp_path):
    vs = _lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        def f(x):
            a = x.sum().item()
            b = x.max().item()
            return a, b
        """,
    )
    only_jit1 = [v for v in vs if v.rule == "JIT001"]
    assert len(only_jit1) == 2
    baseline = {"version": 1, "entries": {only_jit1[0].key: 1}}
    new, stale = compare_to_baseline(only_jit1, baseline)
    assert len(new) == 1  # one allowed, one new
    assert not stale


# --- a kernel written by hand compiles for the chip in tier-1 ---------------


def _modules_calling_pallas(root):
    """Dotted names of the modules under ``root`` whose code calls
    ``pallas_call`` (read from the syntax tree: a comment is no call)."""
    import ast
    import pathlib

    root = pathlib.Path(root)
    found = set()
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Call) and "pallas_call" in (
                getattr(node.func, "id", None), getattr(node.func, "attr", None)
            ):
                rel = path.relative_to(root.parent).with_suffix("")
                found.add(".".join(rel.parts))
    return found


def _modules_imported_by(path):
    import ast

    names = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    return names


def test_every_pallas_call_has_a_chip_compile_case(tmp_path):
    """The v5e compiler refuses what interpret mode lets through, so a
    module that calls ``pallas_call`` is imported by
    ``tests/test_chip_compile.py``, which compiles its kernel for a
    described chip. Today no module does (the set is empty); the rule
    waits for the next kernel, and the finder is shown one here."""
    import os

    repo = os.path.join(os.path.dirname(__file__), "..")
    compiled = _modules_imported_by(
        os.path.join(repo, "tests", "test_chip_compile.py")
    )
    assert "trino_tpu.ops.dense_join" in compiled  # the reader reads imports
    missing = _modules_calling_pallas(os.path.join(repo, "trino_tpu")) - compiled
    assert not missing, f"no compile case in tests/test_chip_compile.py: {missing}"

    pkg = tmp_path / "pkg"
    (pkg / "ops").mkdir(parents=True)
    (pkg / "ops" / "kernel.py").write_text(
        "from jax.experimental import pallas as pl\n"
        "def run(x):\n"
        "    return pl.pallas_call(lambda i, o: None, out_shape=x)(x)\n"
    )
    (pkg / "ops" / "prose.py").write_text("# jax.jit, not pallas_call\n")
    assert _modules_calling_pallas(pkg) == {"pkg.ops.kernel"}
