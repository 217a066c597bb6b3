"""``python -m accelerate_tpu.serve`` — run the OpenAI-compatible front door.

The serving analog of the reference's ``accelerate launch``: one command that
builds N engine replicas, puts the elastic
:class:`~accelerate_tpu.serving.router.ReplicaRouter` behind the
:class:`~accelerate_tpu.serving.api.FrontDoor` driver, and binds the HTTP
edge (:class:`~accelerate_tpu.serving.api.ApiServer`) — completions, chat,
SSE streaming, and the muxed telemetry surface on a single port.

Examples::

    # a tiny random-weight model on an ephemeral port (smoke test)
    python -m accelerate_tpu.serve --preset tiny --port 8000

    # two replicas from a safetensors export, bounded queues
    python -m accelerate_tpu.serve --preset small \
        --checkpoint /ckpts/step-9000 --replicas 2 \
        --max-queue 64 --weights-version step-9000 --port 8000

    curl -N localhost:8000/v1/completions -d \
        '{"prompt": [3, 1, 4, 1, 5], "max_tokens": 8, "stream": true, \
          "temperature": 0}'

Weight hot-swap and replica drain are driver operations, not CLI flags —
see the runbook in ``docs/usage/api_server.md``.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

__all__ = ["build_service", "main", "parse_args"]


def _build_params(model, cfg, seed: int, checkpoint: Optional[str]):
    import jax
    import jax.numpy as jnp

    if checkpoint:
        from .checkpointing import load_model_params

        return load_model_params(checkpoint)
    return model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def build_service(args):
    """Construct (router, frontdoor, server) from parsed CLI args
    (:func:`parse_args`).  Split from :func:`main` so tests, benches and
    ``chip_smoke.py`` can assemble the exact service the CLI would, minus the
    blocking serve loop.

    Where more than one device is present, replica ``i`` is built on the mesh
    of chip ``i`` (:func:`~accelerate_tpu.parallel.mesh.replica_meshes`):
    its params and KV pool live there and nowhere else.  On a single device
    every replica shares it, and the params, as before."""
    import jax
    import jax.numpy as jnp

    from .models.transformer import Transformer, TransformerConfig
    from .parallel.mesh import replica_meshes
    from .serving import ReplicaRouter, ServingEngine
    from .serving.api import ApiServer, FrontDoor

    presets = {
        "tiny": TransformerConfig.tiny,
        "gpt2": TransformerConfig.gpt2,
        "gpt2-xl": TransformerConfig.gpt2_xl_equiv,
        "small": lambda **kw: TransformerConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=4096,
            num_layers=12, num_heads=16, num_kv_heads=16, max_seq_len=512,
            **kw,
        ),
    }
    if args.preset not in presets:
        raise SystemExit(
            f"unknown --preset {args.preset!r}; choose from {sorted(presets)}"
        )
    overrides = {"max_seq_len": args.max_len}
    if args.param_dtype is not None:
        overrides["param_dtype"] = jnp.dtype(args.param_dtype)
    cfg = presets[args.preset](**overrides)
    model = Transformer(cfg)
    params = _build_params(model, cfg, args.seed, args.checkpoint)
    if len(jax.devices()) > 1:
        meshes = replica_meshes(args.replicas)
        # each replica places its own copy on its own chip: stage the weights
        # on the host once, so the default device never holds two
        params = jax.device_get(params)
    else:
        meshes = [None] * args.replicas

    engines = [
        ServingEngine(
            model, params,
            num_slots=args.num_slots,
            max_len=args.max_len,
            decode_window=args.decode_window,
            speculate_k=args.speculate_k,
            max_queue=args.max_queue,
            weights_version=args.weights_version,
            rng_seed=args.seed + i,
            mesh=meshes[i],
        )
        for i in range(args.replicas)
    ]
    router = ReplicaRouter(engines, policy=args.policy)
    frontdoor = FrontDoor(router, model_name=args.model_name).start()
    server = ApiServer(
        frontdoor,
        host=args.host,
        port=args.port,
        unhealthy_after_s=args.unhealthy_after_s,
        request_timeout_s=args.request_timeout_s,
    )
    return router, frontdoor, server


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m accelerate_tpu.serve",
        description="OpenAI-compatible serving front door for accelerate_tpu",
    )
    p.add_argument("--preset", default="tiny",
                   help="model geometry: tiny | small | gpt2 | gpt2-xl")
    p.add_argument("--checkpoint", default=None,
                   help="safetensors directory (save_model export); random "
                        "init when omitted")
    p.add_argument("--model-name", default="accelerate-tpu",
                   help="model id served by /v1/models")
    p.add_argument("--weights-version", default="v0",
                   help="weights label for /v1/models and A/B pinning")
    p.add_argument("--host", default=None,
                   help="bind host (default ATPU_API_HOST or 127.0.0.1)")
    p.add_argument("--port", type=int, default=8000,
                   help="bind port (0 = ephemeral)")
    p.add_argument("--replicas", type=int, default=1)
    p.add_argument("--policy", default="affinity",
                   choices=("affinity", "round_robin"))
    p.add_argument("--num-slots", type=int, default=4)
    p.add_argument("--max-len", type=int, default=512)
    p.add_argument("--decode-window", type=int, default=4)
    p.add_argument("--speculate-k", type=int, default=0)
    p.add_argument("--max-queue", type=int, default=256,
                   help="per-replica admission bound (queue-full -> 429); "
                        "0 = unbounded")
    p.add_argument("--unhealthy-after-s", type=float, default=60.0)
    p.add_argument("--request-timeout-s", type=float, default=600.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--param-dtype", default=None,
                   choices=("float32", "bfloat16"),
                   help="dtype the weights are held in (default: the preset's; "
                        "gpt2-xl's float32 weights alone are 8.5 GB)")
    return p


def parse_args(argv=None) -> argparse.Namespace:
    """The CLI's arguments as :func:`build_service` takes them."""
    args = _parser().parse_args(argv)
    if args.max_queue == 0:
        args.max_queue = None
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    from .utils.environment import enable_compile_cache

    enable_compile_cache()
    router, frontdoor, server = build_service(args)
    print(f"serving {args.model_name} ({args.preset}, "
          f"{args.replicas} replica(s), version {args.weights_version}) "
          f"on {server.url}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("shutting down", flush=True)
    finally:
        server.stop()
        frontdoor.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
