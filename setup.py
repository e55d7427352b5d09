"""Package build (role of the reference's cmake root + paddle/scripts/
docker + deb packaging, re-designed as one Python wheel).

Static metadata lives in pyproject.toml. This file contributes what the
declarative config cannot express:

- the compat-shim package-dir mapping: ``compat/paddle`` and
  ``compat/py_paddle`` install under their reference import names, so
  `from paddle.trainer_config_helpers import *` and
  `import py_paddle.swig_paddle` work unmodified after `pip install`;
- a best-effort prebuild of the native datapath library
  (paddle_tpu/native/datapath.cc → _datapath.so) into the wheel. The
  runtime loader (paddle_tpu/native/__init__.py) prefers the bundled
  library, falls back to build-on-first-import, then to the NumPy
  paths — a missing toolchain at either build or run time never breaks
  the install.
"""

import hashlib
import os
import shutil
import subprocess
import sys

from setuptools import find_packages, setup
from setuptools.command.build_py import build_py
from setuptools.dist import Distribution

# single source of truth for the datapath compile line (the loader's
# build-on-first-import path uses the same helper, so the wheel-bundled
# library can never be compiled with different flags than a cache build)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from paddle_tpu.native import build_command  # noqa: E402


def _have_cxx() -> bool:
    return shutil.which(os.environ.get("CXX", "g++")) is not None


class BuildPyWithDatapath(build_py):
    def run(self):
        super().run()
        if not _have_cxx():
            self.announce("no C++ compiler; datapath prebuild skipped — "
                          "the runtime builds or falls back on first import",
                          level=3)
            return
        src = os.path.join("paddle_tpu", "native", "datapath.cc")
        out = os.path.join(self.build_lib, "paddle_tpu", "native", "_datapath.so")
        try:
            subprocess.run(build_command(src, out), check=True,
                           capture_output=True, timeout=300)
            # stamp the source hash so the runtime loader rejects a
            # bundle that no longer matches datapath.cc (an ABI check
            # alone would let a stale-but-compatible binary shadow an
            # edited source)
            with open(src, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()
            with open(out.replace(".so", ".hash"), "w") as f:
                f.write(digest + "\n")
        except Exception as e:  # noqa: BLE001 — optional artifact
            self.announce(f"datapath prebuild skipped ({e}); the runtime "
                          "will build or fall back on first import", level=3)


class DatapathDistribution(Distribution):
    """A wheel that carries the arch-specific _datapath.so must not be
    tagged py3-none-any — pip would install an x86-64 binary on arm64,
    where CDLL fails and the prebuild benefit is silently lost. When a
    compiler is present (so the prebuild will run) the wheel is declared
    platform-specific; without one it stays pure and the runtime's
    build-on-first-import / NumPy fallback chain applies. (If the
    compile itself fails the wheel is tagged platform-specific without
    the .so — over-restrictive but harmless; the runtime chain still
    applies.)"""

    def has_ext_modules(self):
        return _have_cxx()


try:
    from wheel.bdist_wheel import bdist_wheel as _bdist_wheel

    class BdistWheelCtypes(_bdist_wheel):
        """The bundled library is ctypes-loaded — no CPython ABI — so the
        wheel must stay py3-none-<plat>, not cp3X-cp3X-<plat>: an
        interpreter-specific tag would lock out other supported Python
        versions (requires-python >= 3.11) for no reason."""

        def get_tag(self):
            python, abi, plat = super().get_tag()
            if self.root_is_pure:
                return python, abi, plat
            return "py3", "none", plat

    _wheel_cmdclass = {"bdist_wheel": BdistWheelCtypes}
except ImportError:  # pragma: no cover - wheel not installed
    _wheel_cmdclass = {}


setup(
    packages=find_packages(include=["paddle_tpu*"]) + [
        "paddle",
        "paddle.trainer",
        "paddle.trainer_config_helpers",
        "paddle.utils",
        "py_paddle",
    ],
    package_dir={
        "": ".",
        "paddle": "compat/paddle",
        "py_paddle": "compat/py_paddle",
    },
    cmdclass={"build_py": BuildPyWithDatapath, **_wheel_cmdclass},
    distclass=DatapathDistribution,
)
