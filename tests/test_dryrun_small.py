"""Distributed dry-run smoke: compile every family on an 8-device mesh.

The full 512-device 40-cell dry-run is run by `repro.launch.dryrun --all`
(results in results/dryrun/); this test keeps the same code path honest in
CI-sized time by compiling REDUCED configs on 8 fake devices in a
subprocess (XLA device count must be set before jax initializes, hence the
subprocess).
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import RuntimeFlags, init_cache
from repro.launch.steps import (abstract_params, abstract_opt_state,
                                make_train_step, make_decode_step)
from repro.distributed.sharding import (param_shardings, cache_shardings,
                                        batch_sharding, dp_axes)

out = {}
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for arch in %ARCHS%:
    cfg = get_config(arch).reduced()
    flags = RuntimeFlags(use_pallas=False, interpret=False, remat=True,
                         mesh=mesh, dp=dp_axes(mesh))
    p_shape = abstract_params(cfg)
    p_shard = param_shardings(mesh, p_shape)
    o_shape = abstract_opt_state(p_shape)
    o_shard = param_shardings(mesh, o_shape)
    o_shard["step"] = NamedSharding(mesh, P())
    B, S = 4, 64
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.family == "vlm":
        batch["vision"] = jax.ShapeDtypeStruct(
            (B, cfg.vision_tokens, cfg.vision_dim), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.enc_frames, cfg.d_model), jnp.float32)
    b_shard = {k: (batch_sharding(mesh, B) if v.ndim == 2 else
                   NamedSharding(mesh, P(("data",), None, None)))
               for k, v in batch.items()}
    with mesh:
        c = jax.jit(make_train_step(cfg, flags),
                    in_shardings=(p_shard, o_shard, b_shard)
                    ).lower(p_shape, o_shape, batch).compile()
    # decode path too
    cache = jax.eval_shape(lambda: init_cache(cfg, B, 2 * S))
    c_shard = cache_shardings(mesh, cfg, cache, B)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    flags_d = dataclasses.replace(flags, remat=False)
    with mesh:
        c2 = jax.jit(make_decode_step(cfg, flags_d),
                     in_shardings=(p_shard, batch_sharding(mesh, B), c_shard)
                     ).lower(p_shape, tok, cache).compile()
    out[arch] = "ok"
print(json.dumps(out))
"""


@pytest.mark.parametrize("archs", [
    ["smollm-360m", "granite-moe-1b-a400m"],
    ["rwkv6-1.6b", "zamba2-2.7b"],
    ["whisper-base", "llama-3.2-vision-11b"],
])
def test_small_mesh_compile(archs):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    code = SCRIPT.replace("%ARCHS%", json.dumps(archs))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert all(out[a] == "ok" for a in archs)
