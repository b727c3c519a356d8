"""Switch-MoE causal LM over the data × expert mesh — the EP machinery
(examples/moe_expert_parallel.py shows the bare layer) wired into a real
model family (models/moe_lm.py).

No reference equivalent (the guide predates MoE; SURVEY.md §2c lists EP as
a stretch goal). Tokens are sharded over BOTH mesh axes; expert FFN stacks
live sharded over ``expert`` and the tokens travel to them via all_to_all.

    python examples/switch_moe_lm.py --fake-devices 8
    python examples/switch_moe_lm.py --fake-devices 8 --expert 2
"""

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--num-experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--expert", type=int, default=4,
                    help="expert-axis size (data absorbs the rest)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()

    if args.fake_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.fake_devices:
        jax.config.update("jax_num_cpu_devices", args.fake_devices)
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_tensorflow_guide_tpu.core.dist import initialize
    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        axis_sizes,
        build_mesh,
    )
    from distributed_tensorflow_guide_tpu.models.moe_lm import SwitchLM
    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
    )

    initialize()
    mesh = build_mesh(MeshSpec(data=-1, expert=args.expert))
    sizes = axis_sizes(mesh)
    cfg = TransformerConfig(
        vocab_size=256, num_layers=args.layers, num_heads=4,
        d_model=args.d_model, d_ff=args.d_model * 4, max_len=args.seq_len,
        causal=True, dtype=jnp.float32,
    )
    lm = SwitchLM(mesh, cfg, num_experts=args.num_experts,
                  top_k=args.top_k)
    params = lm.init_params(jax.random.PRNGKey(0))
    tx = optax.adam(args.lr)
    opt_state = lm.init_opt_state(tx, params)
    step = lm.make_train_step(tx, params, donate=False)

    r = np.random.RandomState(0)
    tokens = r.randint(0, cfg.vocab_size,
                       (args.global_batch, cfg.max_len)).astype(np.int32)
    for i in range(args.steps):
        opt_state, params, m = step(opt_state, params, tokens)
        if (i + 1) % 10 == 0:
            print(f"step {i + 1}: lm_loss={float(m['lm_loss']):.4f} "
                  f"load_balance={float(m['load_balance']):.3f}")
    print(f"switch-moe ok: {args.num_experts} experts over "
          f"expert={sizes['expert']} x data={sizes['data']}, final "
          f"lm_loss={float(m['lm_loss']):.4f}")


if __name__ == "__main__":
    main()
