"""Tier-1 collects the benchmark's own tests (benchmarks/tests/) here."""
import importlib.util
import os

_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests")
for _name in ("test_benchmark", "test_launch_metrics"):
    _spec = importlib.util.spec_from_file_location(
        f"benchmarks_tests_{_name}", os.path.join(_TESTS, _name + ".py"))
    _module = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_module)
    globals().update({k: v for k, v in vars(_module).items()
                      if k.startswith("test_")})
