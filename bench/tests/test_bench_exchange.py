"""The four-node cell's check on four CPU devices: a sound lgc_rar ring
run is correct, and one whose exchange between nodes is left out is
not.  Runs in a child process, which gets its four devices before JAX
starts.

The limits below come from CPU readings at this size: sound runs read
loss gaps under 3e-5 and embedding gradient gaps under 3e-4; with the
ring's mean left out the embedding gradient reads 0.90 and more."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import tinybench
from tinybench import BENCH, ROOT

CHILD = textwrap.dedent('''
    import json, shutil, sys, time
    from pathlib import Path
    sys.path[:0] = [{src!r}, {bench!r}]
    import jax
    import run as R
    from lgcbench import spec
    root, broken = Path(sys.argv[1]), sys.argv[2] == "1"
    if broken:
        from repro.dist.transport import RingTransport
        RingTransport.mean = lambda self, x: x
    cell = spec.resolve(root, "tiny-mamba.rar-ring.x4", root / "bench")
    res, _ = R.result(cell, 3, 0.2, False, jax.devices()[:4], time.time(),
                      root, root / "bench")
    print(json.dumps(res))
''')

LIMITS = {"loss": 2e-4, "grad": 0.01, "change": 0.9, "ef": 0.1}


def _run(root, broken: bool) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(src=str(ROOT / "src"), bench=str(BENCH))
    proc = subprocess.run([sys.executable, "-c", code, str(root),
                           "1" if broken else "0"], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_exchange_left_out_is_caught(tmp_path):
    root = tmp_path
    tinybench.make_root(root, "rar-ring.x4",
                        {"method": "lgc_rar", "transport": "ring",
                         "chips": 4, "data_shards": 4}, LIMITS)
    sound = _run(root, broken=False)
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    broken = _run(root, broken=True)
    assert not broken["correct"]
    assert broken["checks"]["grad_gap"]["value"] > LIMITS["grad"]
