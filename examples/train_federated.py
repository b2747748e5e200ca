"""End-to-end driver: federated training of a ~100M-parameter transformer
LM with DeFTA across 4 simulated workers (the production pattern from
launch/train.py at CPU scale).

Each worker holds a private shard of a synthetic token stream; every
``--gossip-every`` steps they exchange params with outdegree-corrected
weights. Run a few hundred steps to watch the per-worker losses converge
together after each gossip.

    PYTHONPATH=src python examples/train_federated.py --steps 300
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import enable_compile_cache
from repro.config import ModelConfig
from repro.core.aggregation import mixing_matrix
from repro.core.gossip import mix_pytree
from repro.core.topology import make_topology
from repro.data.loader import TokenBatcher
from repro.models import model as mm
from repro.optim import make_optimizer

CFG_100M = ModelConfig(
    name="repro-100m", family="dense", num_layers=12, d_model=768,
    num_heads=12, num_kv_heads=4, d_ff=2048, vocab_size=16_384,
    tie_embeddings=True, dtype="float32", scan_layers=False, remat=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--gossip-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    cfg = CFG_100M
    w = args.workers
    n_params = cfg.param_count()
    print(f"model: {cfg.name} ({n_params/1e6:.0f}M params), "
          f"{w} federated workers")

    # per-worker data streams (different seeds = different local corpora)
    batchers = [TokenBatcher(cfg.vocab_size, args.seq, args.batch, seed=i)
                for i in range(w)]
    adj = make_topology("ring", w, 2)
    sizes = np.full(w, args.batch)
    P = jnp.asarray(mixing_matrix(adj, sizes, "defta"), jnp.float32)

    opt = make_optimizer("adam", args.lr)
    key = jax.random.PRNGKey(0)
    params = jax.vmap(lambda k: mm.init_params(k, cfg))(
        jax.random.split(key, w))
    opt_state = jax.vmap(opt.init)(params)

    @jax.jit
    def fl_step(params, opt_state, step, batch):
        def one(p, o, b):
            (loss, _), g = jax.value_and_grad(
                lambda pp: mm.loss_fn(pp, cfg, b), has_aux=True)(p)
            p2, o2 = opt.update(p, g, o, step)
            return p2, o2, loss
        return jax.vmap(one)(params, opt_state, batch)

    gossip = jax.jit(lambda p: mix_pytree(P, p))

    for i in range(args.steps):
        t0 = time.time()
        batch = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[b.batch_at(i) for b in batchers])
        params, opt_state, losses = fl_step(params, opt_state,
                                            jnp.int32(i), batch)
        tag = ""
        if (i + 1) % args.gossip_every == 0:
            params = gossip(params)
            tag = "  [gossip]"
        if i % 5 == 0 or tag:
            print(f"step {i:4d}  losses="
                  f"{[round(float(x), 3) for x in losses]} "
                  f"({time.time()-t0:.1f}s){tag}")
    spread = float(jnp.std(losses))
    print(f"final loss spread across workers: {spread:.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
