"""BERT-Large SQuAD-style fine-tune — upstream's compressed-comm workload.

Counterpart of /root/reference/examples/squad/main.py (BERT-Large SQuAD
fine-tuning, the workload upstream benchmarks ByteGrad/QAdam on).  A span
head (start/end logits) sits on the Transformer encoder; data is
SQuAD-shaped synthetic by default (seq 384, span labels) — pass ``--dataset``
with a tokenized .npz (input_ids, start_positions, end_positions) for real
data.

    python examples/squad_finetune.py --algorithm bytegrad --steps 10
"""

import argparse

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

import bagua_tpu
from bagua_tpu.algorithms.bytegrad import ByteGradAlgorithm
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.algorithms.q_adam import QAdamAlgorithm
from bagua_tpu.models.transformer import TransformerConfig, TransformerLM, bert_large_config


class SquadModel(nn.Module):
    """Encoder trunk + span-extraction head (start/end logits)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, input_ids):
        hidden = TransformerLM(self.cfg, head=False)(input_ids)
        logits = nn.Dense(2, dtype=jnp.float32, name="qa_head")(hidden)
        return logits[..., 0], logits[..., 1]  # start, end: [B, S]


def make_algorithm(name: str, lr: float):
    if name == "bytegrad":
        return ByteGradAlgorithm(hierarchical=False), optax.adamw(lr)
    if name == "qadam":
        return QAdamAlgorithm(warmup_steps=20, lr=lr, hierarchical=False), None
    return GradientAllReduceAlgorithm(), optax.adamw(lr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algorithm", default="bytegrad",
                    choices=["gradient_allreduce", "bytegrad", "qadam"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=384)
    ap.add_argument("--lr", type=float, default=3e-5)
    ap.add_argument("--tiny", action="store_true",
                    help="4-layer config for CPU smoke runs")
    ap.add_argument("--dataset", type=str, default=None,
                    help=".npz with input_ids/start_positions/end_positions")
    args = ap.parse_args()

    bagua_tpu.init_process_group()
    n_dev = len(jax.devices())
    batch = args.batch * n_dev

    if args.tiny:
        cfg = TransformerConfig(vocab_size=1024, d_model=128, n_heads=4,
                                n_layers=4, d_ff=512, max_seq_len=args.seq)
    else:
        cfg = bert_large_config(max_seq_len=args.seq)
    model = SquadModel(cfg)

    if args.dataset:
        # real tokenized SQuAD rows: cycle through the WHOLE file batch by
        # batch (the reference fine-tunes over the real dataset, not one
        # memorized batch; .buildkite benchmark_master.sh:83-153)
        data = np.load(args.dataset)
        n_rows = (len(data["input_ids"]) // batch) * batch
        if n_rows == 0:
            raise SystemExit(f"dataset has fewer than {batch} rows")
        ids = data["input_ids"][:n_rows].astype(np.int32)
        starts = data["start_positions"][:n_rows].astype(np.int32)
        ends = data["end_positions"][:n_rows].astype(np.int32)
    else:
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (batch, args.seq)).astype(np.int32)
        starts = rng.integers(0, args.seq, batch).astype(np.int32)
        ends = np.minimum(starts + rng.integers(1, 16, batch), args.seq - 1).astype(np.int32)

    params = model.init(jax.random.PRNGKey(0), jnp.asarray(ids[:2]))["params"]

    def loss_fn(p, b):
        s_logits, e_logits = model.apply({"params": p}, b["ids"])
        return 0.5 * (
            optax.softmax_cross_entropy_with_integer_labels(s_logits, b["start"]).mean()
            + optax.softmax_cross_entropy_with_integer_labels(e_logits, b["end"]).mean()
        )

    algo, tx = make_algorithm(args.algorithm, args.lr)
    trainer = bagua_tpu.BaguaTrainer(loss_fn, tx, algo)
    state = trainer.init(params)
    n_batches = max(1, len(ids) // batch)
    shards = {}  # shard lazily: only batches --steps actually touches

    def shard(k):
        if k not in shards:
            shards[k] = trainer.shard_batch({
                "ids": ids[k * batch:(k + 1) * batch],
                "start": starts[k * batch:(k + 1) * batch],
                "end": ends[k * batch:(k + 1) * batch],
            })
        return shards[k]

    import time

    losses = []
    t0 = None
    for step in range(args.steps):
        state, loss = trainer.train_step(state, shard(step % n_batches))
        losses.append(float(loss))
        if step == 0:
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0 if args.steps > 1 else float("nan")
    seq_per_sec = (args.steps - 1) * batch / dt
    print(f"algorithm={args.algorithm} first_loss={losses[0]:.4f} "
          f"final_loss={losses[-1]:.4f} throughput={seq_per_sec:.2f} seq/s")
    assert losses[-1] < losses[0], "no learning signal"


if __name__ == "__main__":
    main()
