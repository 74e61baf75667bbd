"""One compile-cache rule, in one place (``trino_tpu/__init__.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and no repo code
sets the directory; where it is not, ``import trino_tpu`` sets
``<checkout>/.jax_cache``. The native library builds into a fixed directory
inside the checkout. Each case is a fresh interpreter: the rule is about
what a process gets at import.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# records every jax.config.update of the cache directory made while
# trino_tpu is imported, then prints what the process ended up with
_PROBE = """
import json, jax
calls = []
real = jax.config.update
def spy(name, value):
    if name == "jax_compilation_cache_dir":
        calls.append(value)
    return real(name, value)
jax.config.update = spy
import trino_tpu
from trino_tpu import native
print(json.dumps({
    "dir": jax.config.jax_compilation_cache_dir,
    "updates": calls,
    "native": native.NATIVE_AVAILABLE,
    "native_lib": getattr(native._LIB, "_name", None),
}))
"""


def _probe(env_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=120, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("env_set", [True, False], ids=["env-set", "env-unset"])
def test_cache_directory_rule(tmp_path, env_set):
    want = str(tmp_path / "xla") if env_set else os.path.join(REPO, ".jax_cache")
    got = _probe(want if env_set else None)
    assert got["dir"] == want
    # with the variable set JAX reads it and no repo code sets the directory
    assert got["updates"] == ([] if env_set else [want])


def test_native_library_builds_inside_the_checkout():
    got = _probe(None)
    assert got["native"], "g++ build of native/columnar.cpp failed"
    lib = got["native_lib"]
    assert os.path.dirname(lib) == os.path.join(REPO, ".cache", "native"), lib
    assert re.fullmatch(r"columnar_[0-9a-f]{16}\.so", os.path.basename(lib))


def _python_files(*roots):
    for root in roots:
        if os.path.isfile(root):
            yield root
            continue
        for d, _, files in os.walk(root):
            yield from (os.path.join(d, f) for f in files if f.endswith(".py"))


def test_no_cache_or_build_path_from_tempfile_pid_or_home():
    offenders = []
    for path in _python_files(
        os.path.join(REPO, "trino_tpu", "native"),
        os.path.join(REPO, "trino_tpu", "__init__.py"),
    ):
        with open(path) as f:
            for i, line in enumerate(f, 1):
                if re.search(r"tempfile|getpid|expanduser|time\.time", line):
                    offenders.append(f"{os.path.relpath(path, REPO)}:{i}")
    assert not offenders, offenders


def test_only_the_package_sets_a_cache_directory():
    """``jax_compilation_cache_dir`` is updated in exactly one place, and
    the package-specific variable of old is gone."""
    setters, old_var = [], []
    roots = [os.path.join(REPO, d) for d in ("trino_tpu", "tests", "scripts")]
    roots += [os.path.join(REPO, f) for f in os.listdir(REPO) if f.endswith(".py")]
    me = os.path.abspath(__file__)
    for path in _python_files(*roots):
        if os.path.abspath(path) == me:
            continue
        text = open(path).read()
        rel = os.path.relpath(path, REPO)
        if re.search(r"update\(\s*[\"']jax_compilation_cache_dir", text):
            setters.append(rel)
        if "TRINO_TPU_COMPILE_CACHE" in text:
            old_var.append(rel)
    assert setters == [os.path.join("trino_tpu", "__init__.py")], setters
    assert not old_var, old_var
