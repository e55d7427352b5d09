"""Packaging surface (pyproject.toml + setup.py + Dockerfile + CI — the
reference's cmake/docker/deb/travis roles, SURVEY §2.11).

A full wheel build is exercised out-of-band (CI `package` job; verified
manually: the wheel carries paddle_tpu, the compat shims under their
reference import names, and the prebuilt native datapath). Here: cheap
invariants that catch drift without paying a build per suite run.
"""

import ast
import os

import tomllib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pyproject():
    with open(os.path.join(ROOT, "pyproject.toml"), "rb") as f:
        return tomllib.load(f)


def test_metadata_and_entry_point():
    meta = _pyproject()
    assert meta["project"]["name"] == "paddle-tpu"
    # console script must point at an importable callable
    target = meta["project"]["scripts"]["paddle"]
    mod, attr = target.split(":")
    m = __import__(mod, fromlist=[attr])
    assert callable(getattr(m, attr))
    # version comes from the single source of truth
    assert meta["tool"]["setuptools"]["dynamic"]["version"]["attr"] == (
        "paddle_tpu.version.__version__"
    )


def test_compat_shim_mapping_matches_tree():
    """setup.py's explicit shim packages must match the compat/ tree —
    a new shim subpackage that isn't listed would silently drop out of
    the wheel."""
    src = open(os.path.join(ROOT, "setup.py")).read()
    listed = {
        n.value
        for n in ast.walk(ast.parse(src))
        if isinstance(n, ast.Constant) and isinstance(n.value, str)
        and (n.value in ("paddle", "py_paddle") or n.value.startswith("paddle."))
    }
    on_disk = set()
    for base, import_name in (("compat/paddle", "paddle"),
                              ("compat/py_paddle", "py_paddle")):
        for dirpath, _dirs, files in os.walk(os.path.join(ROOT, base)):
            if "__init__.py" in files:
                rel = os.path.relpath(dirpath, os.path.join(ROOT, base))
                name = import_name if rel == "." else (
                    import_name + "." + rel.replace(os.sep, ".")
                )
                on_disk.add(name)
    missing = on_disk - listed
    assert not missing, f"compat packages not listed in setup.py: {missing}"


def test_dockerfile_and_ci_reference_real_commands():
    docker = open(os.path.join(ROOT, "Dockerfile")).read()
    assert "pip install" in docker and "ENTRYPOINT" in docker
    ci = open(os.path.join(ROOT, ".github", "workflows", "ci.yml")).read()
    assert "pytest tests/" in ci
    # CLI subcommand used as the container smoke must exist
    from paddle_tpu.cli import main  # noqa: F401
    from paddle_tpu import version
    assert version.__version__
