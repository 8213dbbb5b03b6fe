"""The block-diffusion cell's tests (the rehearsal of
``sdar30b_serve_blockgen``, the manifest's new entries, the ``sdar_moe``
family's counts, the new reducers): they live in
``tests/test_blockgen_bench.py``, where the repository's tier-1 command
finds them, and run here too."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "test_blockgen_bench", os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "tests", "test_blockgen_bench.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
globals().update({k: v for k, v in vars(_mod).items()
                  if k.startswith("test_") or k == "ctx"})
