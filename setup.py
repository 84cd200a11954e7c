"""Build shim: compile the native hot-path library at install time.

native/chanamq_native.cpp is a plain `extern "C"` shared object consumed via
ctypes (chanamq_tpu/native_ext.py), not a CPython extension module — so it is
compiled with build_ext machinery but never imported. A missing/broken C++
toolchain must not fail the install: the broker runs on its pure-Python hot
paths (native_ext logs a WARNING and falls back), so build errors just skip
the lib.
"""

from setuptools import Extension, setup
from setuptools.command.build_ext import build_ext


class OptionalBuildExt(build_ext):
    def build_extension(self, ext):
        try:
            super().build_extension(ext)
        except Exception as exc:  # toolchain missing: pure-Python fallback
            print(f"WARNING: skipping native extension {ext.name}: {exc}")

    def get_export_symbols(self, ext):
        # not a CPython module: there is no PyInit_* symbol to export
        return []


setup(
    ext_modules=[
        Extension(
            "chanamq_tpu._chanamq_native",
            sources=["native/chanamq_native.cpp"],
            extra_compile_args=["-O2", "-std=c++17"],
            optional=True,
        )
    ],
    cmdclass={"build_ext": OptionalBuildExt},
)
