"""Small cuts of the benchmark's cells that the CPU can run in a test,
with the cells' own limits: the widths stay, the world and the run are
cut."""
import os

import run as harness


def engine_cell():
    """cnn21.paper at its published widths (28×28 images, width 16, batch
    32, 200 samples a worker) with 2 workers plus the attacker and 3 local
    epochs."""
    found = harness.find_cell(harness.load_json(
        os.path.join(harness.ROOT, "BENCHMARK.json")), "cnn21.paper")
    found["config"].update(workers=2, local_epochs=3, test_size=64)
    return found


GOSSIP_EVERY = (4, 1)


def pods_cell(gossip_every: int = 4):
    """qwen3.pods2.gossip4 with Qwen3's block at toy widths (2 layers),
    gossiping every ``gossip_every`` steps (1: the reference follows three
    steps, each with its round, and carries the EF21 residuals between
    them)."""
    found = harness.find_cell(harness.load_json(
        os.path.join(harness.ROOT, "BENCHMARK.json")), "qwen3.pods2.gossip4")
    found["traffic"]["gossip_every"] = gossip_every
    found["config"]["model"].update(num_layers=2, d_model=64, num_heads=4,
                                    num_kv_heads=2, head_dim=16, d_ff=128,
                                    vocab_size=512)
    found["config"].update(seq=32, global_batch=4)
    return found
