"""BERT-base pretraining (masked-LM + NSP) on the fused SPMD path.

ref: GluonNLP scripts/bert/run_pretraining.py — phase-1 recipe (seq 128,
~15% masked, LAMB), here over parallel.TrainStep so forward+backward+LAMB
compile into one XLA program on a device mesh.  Synthetic masked batches
stand in for the tokenized corpus (zero-egress environment); swap
``synthetic_batch`` for a real tokenizer pipeline to train for real.

    python examples/bert_pretrain.py [--layers 12] [--batch-size 64]
    # long sequences: ring/Ulysses sequence parallelism
    python examples/bert_pretrain.py --attention flash --seq-len 2048
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.model_zoo.bert import BERTModel, BERTPretrainLoss


def synthetic_batch(rng, batch, seq_len, n_pred, vocab):
    tok = mx.nd.array(rng.randint(0, vocab, (batch, seq_len))
                      .astype(np.int32))
    tt = mx.nd.array(rng.randint(0, 2, (batch, seq_len)).astype(np.int32))
    vl = mx.nd.array(np.full((batch,), seq_len, np.int32))
    mpos = mx.nd.array(rng.randint(0, seq_len, (batch, n_pred))
                       .astype(np.int32))
    mlab = mx.nd.array(rng.randint(0, vocab, (batch, n_pred))
                       .astype(np.int32))
    mw = mx.nd.array(np.ones((batch, n_pred), np.float32))
    nsp = mx.nd.array(rng.randint(0, 2, (batch,)).astype(np.int32))
    return (tok, tt, vl, mpos), (mlab, mw, nsp)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--units", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--num-steps", type=int, default=40)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--attention", default="dense",
                    choices=["dense", "flash", "ring", "ulysses"])
    args = ap.parse_args()

    import jax
    mx.config.setup_compile_cache()
    n_dev = len(jax.devices())
    vocab, n_pred = 30522, max(1, int(args.seq_len * 0.15 * 0.9) // 8 * 8)

    net = BERTModel(vocab_size=vocab, units=args.units,
                    hidden_size=args.units * 4, num_layers=args.layers,
                    num_heads=args.heads, max_length=max(512, args.seq_len),
                    dropout=0.1, attention_impl=args.attention)
    net.initialize()
    net.cast("bfloat16")
    loss_blk = BERTPretrainLoss()

    def loss_fn(out, labels):
        nsp_scores, mlm_scores = out[2], out[3]
        mlm_labels, mlm_weights, nsp_labels = labels
        return loss_blk(mlm_scores, nsp_scores, mlm_labels, mlm_weights,
                        nsp_labels)

    mesh = parallel.make_mesh(dp=n_dev)
    opt = mx.optimizer.create("lamb", learning_rate=args.lr, wd=0.01)
    step = parallel.TrainStep(net, loss_fn, opt, mesh=mesh)

    rng = np.random.RandomState(0)
    x, labels = synthetic_batch(rng, args.batch_size, args.seq_len, n_pred,
                                vocab)
    print("compiling...")
    loss = step(x, labels)
    loss.asnumpy()
    t0 = time.perf_counter()
    for i in range(args.num_steps):
        loss = step(x, labels)
        if i % 10 == 0:
            print(f"step {i}: loss={float(loss.asnumpy()):.3f}")
    loss.asnumpy()
    dt = time.perf_counter() - t0
    print(f"{args.batch_size * args.seq_len * args.num_steps / dt / n_dev:,.0f}"
          f" tokens/s/chip ({n_dev} device(s), attention={args.attention})")


if __name__ == "__main__":
    main()
