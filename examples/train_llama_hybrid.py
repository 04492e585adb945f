"""Pretrain a tiny Llama with hybrid parallelism on an 8-device mesh
(dp=2 x mp=4) — the same SpmdTrainer chip_smoke.py drives on real TPU.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python examples/train_llama_hybrid.py
(on a TPU pod slice, drop both variables and size the mesh to the chips)
"""
import numpy as np

import paddle_tpu as paddle


def main():
    import jax
    if jax.device_count() < 8:
        raise SystemExit(
            f"this example lays a dp=2 x mp=4 mesh over 8 devices; JAX "
            f"sees {jax.device_count()} x {jax.devices()[0].platform}. "
            "See the docstring for the 8-virtual-CPU-device command.")
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel import SpmdTrainer, make_hybrid_mesh

    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=512, hidden_size=128, layers=4,
                           heads=8, kv_heads=4, seq=256)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    optimizer = opt.AdamW(learning_rate=3e-4,
                          parameters=model.parameters())

    mesh = make_hybrid_mesh(dp=2, mp=4)
    trainer = SpmdTrainer(
        model, optimizer,
        lambda m, ids, labels: m.forward_loss(ids, labels),
        mesh=mesh,
        remat_layers=list(model.model.layers), remat_policy="dots")

    rng = np.random.default_rng(0)
    for step in range(5):
        ids = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (8, 256)).astype(np.int32))
        loss = trainer.train_step(ids, ids)
        print(f"step {step}: loss={float(loss.numpy()):.4f}")


if __name__ == "__main__":
    main()
