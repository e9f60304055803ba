"""`chip_smoke.py` off the chip: it refuses to report, and its four-chip
phase is rehearsed on four virtual CPU devices.

The script itself only runs to its result on a TPU; these tests pin that
it fails loudly elsewhere (never printing ``"ok": true``), and run the
`--chips 4` comparison — one-device engine vs bank-sharded and
query-parallel copies, bit for bit — through its own functions at a small
size, so the four-chip path is rehearsed before it costs chip time.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(args, extra_env=None, code=None, timeout=600):
    env = {"PYTHONPATH": str(REPO / "src"),
           "HOME": os.environ.get("HOME", str(REPO)),
           "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu"}
    env.update(extra_env or {})
    cmd = ([sys.executable, "-c", code] if code is not None
           else [sys.executable, str(REPO / "chip_smoke.py"), *args])
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=timeout)


@pytest.mark.parametrize("extra_env,why", [
    ({}, "not a TPU"),
    ({"REPRO_PALLAS": "ref"}, "kernel overrides set"),
    ({"REPRO_PALLAS_STREAMING_NNS": "interpret"}, "kernel overrides set"),
], ids=["cpu", "global_override", "per_op_override"])
def test_chip_smoke_refuses_off_chip(extra_env, why):
    out = _run([], extra_env)
    assert out.returncode != 0, out.stdout
    assert why in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout


def test_chip_smoke_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent("""
        import json, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, os.getcwd())
        import jax
        import chip_smoke as cs
        from repro.data import synthetic
        from repro.models import recsys as rs

        sizes = cs.Sizes(train_steps=20, train_batch=64, serve_batch=32,
                         catalog_items=1 << 15, clusters=8,
                         catalog_requests=128, delta_capacity=64,
                         updates=16)
        cfg = rs.YoutubeDNNConfig(
            n_items=300, user_features={"user_id": 200, "gender": 3},
            history_len=8)
        data = synthetic.make_movielens(n_users=200, n_items=300,
                                        history_len=8)
        data.user_feats = {k: v for k, v in data.user_feats.items()
                           if k in cfg.user_features}
        engine, _ = cs.train_and_build(cfg, data, sizes, 0)
        grown, queries = cs.catalog_setup(engine, data, sizes, 0)
        live = cs.make_live(grown, sizes, 4)
        four = cs.four_chip_phase(live, queries, sizes, jax.devices()[:4])
        print("RESULT", json.dumps(four))
    """)
    out = _run([], code=code)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("RESULT"))
    four = json.loads(line.split(" ", 1)[1])
    assert set(four) == {"banks", "qp"}
    assert four["banks"]["item_sigs"] == [0, 1, 2, 3]  # the bank shards
    assert four["qp"]["item_table"] == [0]
    assert set(four["qp"]["memory"]) == {"0", "1", "2", "3"}
