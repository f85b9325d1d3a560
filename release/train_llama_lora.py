"""BASELINE config 5 — Llama-2-7B LoRA fine-tune on a pod-slice mesh.

Reference-equivalent: the DeepSpeed-LoRA multi-host config from
BASELINE.json, built the TPU-native way (SURVEY §2.9): base weights
frozen + sharded over a dp×tp jax mesh (NamedSharding), tiny LoRA A/B
adapters trained, grads psum'd inside the jitted step on ICI. On CPU this
runs the tiny config over the virtual 8-device mesh (the hostless twin);
on a real v4 slice pass --full for Llama-2-7B dims.

Prints one JSON line: {"tokens_per_s": ..., "lora_params": ...}.
"""

import json
import sys
import time


def main(full: bool = False):
    import os

    if "--full" in sys.argv:
        full = True
    if not full:
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models.lora import (
        LoRAConfig, init_lora, lora_loss, num_lora_params,
    )
    from ray_tpu.models.transformer import (
        TransformerConfig, init_params, param_logical_dims,
    )

    devices = np.array(jax.devices())
    n = len(devices)
    dp, tp = (n // 2, 2) if n >= 2 else (1, 1)
    mesh = Mesh(devices.reshape(dp, tp), ("dp", "tp"))

    if full:
        # Llama-2-7B's widths.
        config = TransformerConfig(
            vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=32, hidden_dim=11008, max_seq=2048, dtype=jnp.bfloat16,
        )
        batch, seq, steps = dp * 1, 2048, 10
        import bench_env
        if bench_env.smoke():
            seq, steps = 256, 2
    else:
        config = TransformerConfig.tiny()
        batch, seq, steps = dp * 2, min(64, config.max_seq), 5
    lora_config = LoRAConfig(rank=8)

    # Shard base params by logical dims: tensor-parallel over 'tp' for the
    # wide matmuls, replicated elsewhere (ZeRO-ish: frozen base needs no
    # optimizer state at all).
    logical = param_logical_dims(config)

    def spec_for(dims):
        if dims is None:
            return P()
        axes = [
            "tp" if d in ("mlp", "heads", "kv", "vocab") else None
            for d in dims
        ]
        return P(*axes)

    import jax.tree_util as jtu

    params = init_params(config, jax.random.PRNGKey(0))

    def map_with_logical(params, logical):
        out = {}
        for key, value in params.items():
            sub = logical.get(key) if isinstance(logical, dict) else None
            if isinstance(value, dict):
                out[key] = map_with_logical(value, sub or {})
            else:
                out[key] = jax.device_put(
                    value, NamedSharding(mesh, spec_for(sub))
                )
        return out

    params = map_with_logical(params, logical)
    adapters = init_lora(config, lora_config, jax.random.PRNGKey(1))
    adapters = jax.device_put(
        adapters, NamedSharding(mesh, P())
    )
    optimizer = optax.adamw(1e-4)
    opt_state = optimizer.init(adapters)

    data_sharding = NamedSharding(mesh, P("dp", None))

    @jax.jit
    def step(params, adapters, opt_state, tokens):
        loss, grads = jax.value_and_grad(lora_loss, argnums=1)(
            params, adapters, tokens, config, lora_config
        )
        updates, opt_state = optimizer.update(grads, opt_state, adapters)
        return optax.apply_updates(adapters, updates), opt_state, loss

    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        rng.integers(0, config.vocab_size, size=(batch, seq + 1)).astype(np.int32),
        data_sharding,
    )
    adapters, opt_state, loss = step(params, adapters, opt_state, tokens)
    jax.block_until_ready(loss)
    start = time.perf_counter()
    for _ in range(steps):
        adapters, opt_state, loss = step(params, adapters, opt_state, tokens)
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - start
    print(json.dumps(
        {
            "benchmark": "train_llama_lora",
            "tokens_per_s": steps * batch * seq / elapsed,
            "lora_params": num_lora_params(adapters),
            "mesh": {"dp": dp, "tp": tp},
            "loss": float(loss),
            "full_model": full,
        }
    ))


if __name__ == "__main__":
    main()
