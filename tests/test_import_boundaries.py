"""One-way imports: the scheduler never loads JAX or the language-model runtime.

The layers point down: service, sweep, forecast -> fleet -> core/rl (JAX)
-> core/batched (JAX only once a step runs) -> core (host, numpy).  The
language-model runtime may import the scheduler, never the reverse.  Each
case imports a scheduler package in a fresh interpreter and checks what
came with it: sweep workers and the service import these packages, and
whatever they drag in is paid by every process that starts.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

#: the language-model runtime: none of it may load with the scheduler
LM_PACKAGES = (
    "models", "kernels", "launch", "distributed", "cluster",
    "checkpoint", "data", "analysis", "configs",
)

_CHECK = (
    "import sys\n"
    "lm = {lm!r}\n"
    "bad = sorted(m for m in sys.modules if m == 'jax' or m.startswith('jax.')\n"
    "             or any(m == 'repro.' + p or m.startswith('repro.' + p + '.') for p in lm))\n"
    "assert not bad, bad\n"
)


@pytest.mark.parametrize(
    "load",
    [
        "import repro.core",
        "import repro.core.batched",
        "import repro.fleet",
        "import repro.forecast",
        "import repro.sweep",
        "import repro.service",
        "from repro.sweep.cells import make_policy; make_policy('heuristic')",
    ],
)
def test_the_scheduler_loads_neither_jax_nor_the_lm_runtime(load):
    code = load + "\n" + _CHECK.format(lm=LM_PACKAGES)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr
