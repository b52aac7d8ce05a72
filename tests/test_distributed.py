"""Multi-device behaviors via subprocesses with xla_force_host_platform_device_count.

Covers: elastic re-mesh on resume (train on a 4-device data axis, resume on 8),
a miniature dry-run (lower+compile on a (pod,data,model) mesh with the real rules
machinery), and the int8-compressed all-reduce under shard_map.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path


SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_py(code: str, n_devices: int, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout


def test_elastic_remesh_resume(tmp_path):
    """Checkpoint from a data=4 mesh resumes bit-compatibly on data=8."""
    code = f"""
    import dataclasses, json
    import jax
    from repro.configs import get_config
    from repro.dist.sharding import make_rules
    from repro.launch.mesh import make_mesh
    from repro.optim import AdamWConfig
    from repro.train import Trainer, TrainerConfig

    cfg = dataclasses.replace(get_config("olmo-1b").reduced(), dtype="float32")
    def mk(n_data):
        mesh = make_mesh((n_data,), ("data",))
        rules = make_rules("train", mesh)
        return mesh, rules

    tcfg = TrainerConfig(seq_len=32, global_batch=8, steps={{steps}},
                         ckpt_every=4, log_every=100, ckpt_async=False)
    ocfg = AdamWConfig(peak_lr=1e-3, warmup=4, total_steps=12)
    mesh, rules = mk({{n_data}})
    tr = Trainer(cfg, tcfg, ocfg, ckpt_dir="{tmp_path}/ckpt",
                 mesh=mesh, rules=rules, log=lambda s: None)
    out = tr.run()
    print(json.dumps({{"first_step": tr.history[0]["step"],
                       "final_loss": out["final_loss"]}}))
    """
    out1 = run_py(code.replace("{steps}", "8").replace("{n_data}", "4"), 8)
    r1 = json.loads(out1.strip().splitlines()[-1])
    assert r1["first_step"] == 0
    # resume the same checkpoint directory on an 8-way data mesh
    out2 = run_py(code.replace("{steps}", "12").replace("{n_data}", "8"), 8)
    r2 = json.loads(out2.strip().splitlines()[-1])
    assert r2["first_step"] == 8              # resumed, re-sharded, continued
    assert r2["final_loss"] < r1["final_loss"] + 0.1


def test_miniature_multipod_dryrun():
    """run the real build_cell machinery on a (pod=2, data=2, model=2) mesh."""
    code = """
    import dataclasses, json
    import jax
    from repro.configs import get_config, SHAPES
    from repro.dist.sharding import make_rules
    from repro.launch.dryrun import build_cell, parse_collective_bytes
    from repro.launch.mesh import make_mesh

    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(), dtype="float32")
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    rules = make_rules("train", mesh)
    fn, args, in_sh, out_sh, donate = build_cell(
        cfg, shape, mesh, rules, grad_accum=2, opt_dtype="float32")
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate).lower(*args).compile()
        coll = parse_collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    print(json.dumps({"collectives": coll["count"],
                      "coll_bytes": coll["total"],
                      "args": int(mem.argument_size_in_bytes)}))
    """
    out = json.loads(run_py(code, 8).strip().splitlines()[-1])
    assert out["collectives"] > 0             # grads reduce across pod/data
    assert out["coll_bytes"] > 0


def test_miniature_decode_cell_with_cache_shardings():
    code = """
    import dataclasses, json
    import jax
    from repro.configs import get_config, SHAPES
    from repro.dist.sharding import make_rules
    from repro.launch.dryrun import build_cell
    from repro.launch.mesh import make_mesh

    cfg = dataclasses.replace(get_config("qwen2.5-32b").reduced(), dtype="float32")
    shape = dataclasses.replace(SHAPES["decode_32k"], seq_len=128, global_batch=4)
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules("serve_tp", mesh)
    fn, args, in_sh, out_sh, donate = build_cell(
        cfg, shape, mesh, rules, grad_accum=1, opt_dtype="float32")
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate).lower(*args).compile()
    print(json.dumps({"ok": 1,
                      "out_bytes": int(compiled.memory_analysis().output_size_in_bytes)}))
    """
    out = json.loads(run_py(code, 8).strip().splitlines()[-1])
    assert out["ok"] == 1


def test_moe_local_dispatch_matches_global():
    """With non-binding capacity, per-shard dispatch == global dispatch."""
    code = """
    import dataclasses, json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.dist.sharding import make_rules, use_rules
    from repro.launch.mesh import make_mesh
    from repro.models.layers import init_tree
    from repro.models.moe import moe_forward, moe_specs

    base = get_config("kimi-k2-1t-a32b").reduced()
    cfg = dataclasses.replace(base, dtype="float32", d_model=32,
        moe=dataclasses.replace(base.moe, n_experts=4, top_k=2, d_ff_expert=16,
                                n_shared_experts=0, first_k_dense=0))
    p = init_tree(moe_specs(cfg, jnp.float32), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))
    mesh = make_mesh((4, 2), ("data", "model"))
    rules_g = make_rules("train", mesh)
    rules_l = make_rules("train", mesh, **{"moe_dispatch": "local"})

    def run(rules):
        def f(p, x):
            with use_rules(rules, mesh):
                y, aux = moe_forward(cfg, p, x, capacity_factor=100.0)
            return y, aux
        with mesh:
            return jax.jit(f)(p, x)

    yg, auxg = run(rules_g)
    yl, auxl = run(rules_l)
    err = float(jnp.max(jnp.abs(yg - yl)))
    print(json.dumps({"err": err, "auxg": float(auxg), "auxl": float(auxl)}))
    """
    out = json.loads(run_py(code, 8).strip().splitlines()[-1])
    assert out["err"] < 1e-4, out
    assert abs(out["auxg"] - out["auxl"]) < 1e-4


def test_distributed_flash_decode_matches_ref():
    """LSE-merge over a sequence-sharded cache == single-device decode attention."""
    code = """
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.dist.flash_decode import decode_attention_seqsharded
    from repro.kernels import ref
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    B, S, Hq, Hkv, D = 2, 64, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, Hq, D))
    kc = jax.random.normal(ks[1], (B, Hkv, S, D))
    vc = jax.random.normal(ks[2], (B, Hkv, S, D))
    length = jnp.array([37, 64], jnp.int32)

    with mesh:
        got = jax.jit(lambda *a: decode_attention_seqsharded(
            *a, mesh=mesh, axis="model"))(q, kc, vc, length)
    want = ref.decode_attention(q, kc, vc, length)
    err = float(jnp.max(jnp.abs(got - want)))
    print(json.dumps({"err": err}))
    """
    out = json.loads(run_py(code, 8).strip().splitlines()[-1])
    assert out["err"] < 1e-4, out


def test_compressed_allreduce_under_shard_map():
    code = """
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import compressed_allreduce
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("pod",))
    x = jnp.arange(8 * 33, dtype=jnp.float32).reshape(8, 33) / 7.0

    def f(xs):
        return compressed_allreduce(xs[0], "pod")[None]

    y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod", None),
                              out_specs=P("pod", None)))(x)
    want = x.mean(axis=0)
    got = np.asarray(y[0])
    rel = np.abs(got - np.asarray(want)).max() / np.abs(np.asarray(want)).max()
    print(json.dumps({"rel": float(rel)}))
    """
    out = json.loads(run_py(code, 8).strip().splitlines()[-1])
    assert out["rel"] < 0.05, out             # int8 wire quantization error bound
