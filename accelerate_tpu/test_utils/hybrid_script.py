"""Hybrid dcn×ici mesh worker: N processes × M local devices, mesh axes
spanning BOTH process (dcn) and local (ici) boundaries — the actual pod shape
(reference approximates it with ``tpu_pod_launcher``,
``commands/launch.py:827-883``).

Launch it through the CLI:

    accelerate-tpu launch --cpu --num_processes 2 --num_cpu_devices 4 \\
        --mesh dp=2,fsdp=4 --dcn_mesh dp=2 hybrid_script.py --out loss.json

Runs one compiled train step of the tiny flagship transformer on a
deterministic batch and writes the (globally reduced) loss + mesh facts from
the main process; the caller asserts loss parity against a monolithic
single-process run of the same step.
"""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax

import accelerate_tpu as at
from accelerate_tpu.models.transformer import Transformer, TransformerConfig, lm_loss_fn


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", required=True)
    args = parser.parse_args()

    # mesh comes from ACCELERATE_(DCN_)MESH; the fsdp plugin activates weight
    # sharding over the local (ici) axis
    acc = at.Accelerator(
        mixed_precision="bf16",
        fsdp_plugin=at.FullyShardedDataParallelPlugin(min_weight_size=1024),
    )
    state_facts = {
        "num_processes": acc.state.num_processes,
        "global_devices": jax.device_count(),
        "local_devices": jax.local_device_count(),
        "mesh_shape": dict(acc.state.mesh.shape),
    }

    cfg = TransformerConfig.tiny()
    model = Transformer(cfg)
    input_ids = jnp.ones((8, 32), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), input_ids)["params"]
    state = acc.create_train_state(params=params, tx=optax.adamw(1e-4), seed=0)
    specs = {str(s.sharding.spec) for s in jax.tree_util.tree_leaves(state.params)}

    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    dl = acc.prepare(at.SimpleDataLoader([{"input_ids": b} for b in data], batch_size=8))
    step = acc.compile_train_step(lm_loss_fn(model), max_grad_norm=1.0)
    for batch in dl:
        state, metrics = step(state, batch)
        break
    loss = float(jax.device_get(metrics["loss"]))

    if acc.is_main_process:
        with open(args.out, "w") as f:
            json.dump({"loss": loss, "param_specs": sorted(specs), **state_facts}, f)
    acc.wait_for_everyone()
    print(f"hybrid worker rank {acc.process_index}: loss={loss:.4f}")


if __name__ == "__main__":
    main()
