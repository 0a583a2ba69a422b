"""HLO analyzer correctness + multi-device sharding integration (spawned
with fake XLA devices in a subprocess so the main process keeps 1 CPU)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze_hlo, roofline_terms


def test_trip_weighted_flops_exact():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    hlo = jax.jit(f).lower(x, ws).compile().as_text()
    ana = analyze_hlo(hlo)
    assert ana["flops"] == 10 * 2 * 128 ** 3  # exactly trip-weighted
    assert ana["collectives"]["total"] == 0.0


def test_roofline_terms_dominance():
    t = roofline_terms(197e12, 10e9, 0.0)   # 1s compute, tiny memory
    assert t["dominant"] == "compute"
    t = roofline_terms(1e9, 819e9 * 2, 0.0)
    assert t["dominant"] == "memory"
    t = roofline_terms(1e9, 1e9, 50e9 * 3)
    assert t["dominant"] == "collective"


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.models.layers import ParallelCtx
    from repro.models.model import Model
    from repro.parallel.sharding import make_rules, named_sharding_tree
    from repro.train.optimizer import OptConfig
    from repro.train.trainstep import make_train_step

    mesh = make_local_mesh(data=4, model=2)
    cfg = get_config("qwen3_moe_235b_a22b").reduced().with_(
        n_experts=4, experts_per_token=2, d_model=64, d_ff=32,
        vocab_size=512, scan_layers=True, n_layers=2)
    rules = make_rules(cfg)
    ctx = ParallelCtx(mesh, rules)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    params = jax.device_put(params, named_sharding_tree(mesh, m.pspecs(rules)))
    init_opt, step = make_train_step(m, OptConfig(lr=1e-3), ctx)
    opt = init_opt(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, 512)
    batch = {"tokens": toks[:, :16], "targets": toks[:, 1:]}
    sfun = jax.jit(step, donate_argnums=(0, 1))
    losses = []
    for i in range(4):
        params, opt, mt = sfun(params, opt, batch, jnp.int32(i))
        losses.append(float(mt["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses

    # single-device reference must agree with the sharded step (1 step)
    m2 = Model(cfg)
    p2 = m2.init(jax.random.PRNGKey(0))
    ctx2 = ParallelCtx()
    init2, step2 = make_train_step(m2, OptConfig(lr=1e-3), ctx2)
    o2 = init2(p2)
    p2b, _, mt2 = jax.jit(step2)(p2, o2, batch, jnp.int32(0))
    print("OK", losses[0], float(mt2["loss"]))
    assert abs(losses[0] - float(mt2["loss"])) < 2e-2
""")


def test_sharded_training_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
