"""The sketch engine's module stands alone: importing it loads no part of
the language-model stack (checked in a fresh interpreter, since this test
process may have loaded those modules already)."""

import os
import subprocess
import sys


def test_engine_module_loads_no_language_model():
    code = ("import sys, repro.serve.engine\n"
            "print(sorted(m for m in sys.modules if m.startswith(("
            "'repro.models', 'repro.configs', 'repro.serve.serve_step'))))")
    src = os.path.join(os.path.dirname(__file__), "..", "..", "src")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]", out.stdout
