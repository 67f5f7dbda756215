"""TPU-fabric multicast benchmark (fig. 3b adapted): collective bytes and
op counts for unicast / sw_tree / hw distribution of a 4 MiB buffer
along an 8-way axis, counted from compiled HLO in a subprocess with
8 fake CPU devices (the parent process stays single-device).  The child
is pinned to the CPU backend: it counts HLO by design, and must never
contend with its parent for an accelerator."""
import json
import os
import subprocess
import sys

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.dist.mcast import bytes_model, make_broadcast_fn
from repro.launch.hlo import analyze_compiled
from benchmarks.analysis import LINK_BW

mesh = jax.make_mesh((8,), ("data",))
x = jnp.zeros((2048, 1024), jnp.bfloat16)  # 4 MiB payload
predicted = bytes_model(x.nbytes, 8, per_device=True)
out = {}
for mode in ("unicast", "sw_tree", "hw"):
    f = make_broadcast_fn(mesh, x.shape, x.dtype, mode)
    with jax.set_mesh(mesh):
        c = jax.jit(f).lower(x).compile()
    a = analyze_compiled(c, 8)
    out[mode] = {
        "collective_bytes_per_dev": a["collective_bytes"],
        "predicted_bytes_per_dev": predicted[mode],
        "counts": a["collective_counts"],
        "est_time_us": a["collective_bytes"] / LINK_BW * 1e6,
    }
print("RESULT " + json.dumps(out))
"""


def run() -> list[str]:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=f"{root}/src:{root}", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True, text=True,
        timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"collective-bytes child failed (rc {proc.returncode}):\n"
            f"{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            data = json.loads(line[len("RESULT "):])
            rows = []
            uni = data["unicast"]["collective_bytes_per_dev"]
            for mode, d in data.items():
                ratio = uni / d["collective_bytes_per_dev"] if d["collective_bytes_per_dev"] else float("inf")
                obs, pred = d["collective_bytes_per_dev"], d["predicted_bytes_per_dev"]
                rows.append(
                    f"fig3b_tpu_{mode},{d['est_time_us']:.1f},"
                    f"bytes/dev={obs/1e6:.1f}MB "
                    f"model={pred/1e6:.1f}MB ({obs/pred:.2f}x pred) "
                    f"ops={d['counts']} speedup_vs_unicast={ratio:.1f}x"
                )
            return rows
    raise RuntimeError(f"collective-bytes child printed no RESULT line:\n"
                       f"{proc.stdout[-3000:]}")
