"""Serve a small causal LM with continuous batching + a paged KV cache.

ref: no reference equivalent — the 1.x stack has no autoregressive
serving at all.  This is the ISSUE 10 runtime end to end: train the
functional ``model_zoo.causal_lm`` transformer for a few hundred SGD
steps on a synthetic successor-chain task (plain ``jax.grad`` over the
param dict — the functional model trains without any Module plumbing),
then serve it through a ``GenerationServer``: prompts prefill through
the bucket grid, every decode step runs ONE pinned executable whatever
the in-flight mix, K/V lives in the shared page pool, and the census
(prefill buckets + 1) bounds the jit cache forever.

    python examples/serve_llm.py [--requests 32] [--clients 4]
"""
import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

VOCAB = 32


def successor(t):
    """The ground-truth next token: a fixed permutation chain of the
    vocabulary (7 is coprime to 32, so every token has one successor
    and the chain visits all 32 before repeating)."""
    return (t * 7 + 3) % VOCAB


def train_quick(cfg, steps=300, batch=32, seq=16, lr=0.5, seed=0):
    """A few hundred SGD steps teaching the LM the successor chain —
    enough that served generations visibly continue it."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.causal_lm import (init_causal_lm,
                                                     sequence_logits)

    params = init_causal_lm(cfg, seed=seed)

    def batch_tokens(key):
        t = jax.random.randint(key, (batch, 1), 0, VOCAB)
        rows = [t]
        for _ in range(seq):
            rows.append(successor(rows[-1]))
        return jnp.concatenate(rows, axis=1)       # [batch, seq+1]

    def loss_fn(p, toks):
        x, y = toks[:, :-1], toks[:, 1:]
        logp = jax.nn.log_softmax(sequence_logits(p, cfg, x), axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()

    @jax.jit
    def step(p, key):
        toks = batch_tokens(key)
        loss, grads = jax.value_and_grad(loss_fn)(p, toks)
        return jax.tree.map(lambda w, g: w - lr * g, p, grads), loss

    key = jax.random.PRNGKey(seed + 1)
    for i in range(steps):
        key, sub = jax.random.split(key)
        params, loss = step(params, sub)
        if (i + 1) % 100 == 0:
            print(f"  train step {i + 1}: loss {float(loss):.3f}")
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32,
                    help="total requests across all clients")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--train-steps", type=int, default=300)
    ap.add_argument("--deadline", type=float, default=5.0)
    args = ap.parse_args()

    from mxnet_tpu import serving
    from mxnet_tpu.config import setup_compile_cache
    from mxnet_tpu.gluon.model_zoo.causal_lm import CausalLMConfig

    setup_compile_cache()
    cfg = CausalLMConfig(vocab_size=VOCAB, n_layers=2, n_heads=2,
                         head_dim=16, d_ff=64)
    print(f"training a {cfg.n_layers}-layer causal LM on the successor "
          f"chain ...")
    params = train_quick(cfg, steps=args.train_steps)

    srv = serving.GenerationServer(
        params, cfg, buckets=serving.BucketSpec(batch=(1, 2),
                                                length=(8, 16)),
        n_slots=4, n_pages=33, page_size=8, max_new_tokens=10,
        default_deadline=args.deadline, seed=0, name="ServeLLM")
    srv.start()
    print(f"serving: census {srv.census()} executables "
          f"(prefill grid + 1 decode), ready={srv.ready()}")

    results, lock = [], threading.Lock()
    per_client = -(-args.requests // args.clients)

    def client(k):
        rng = np.random.RandomState(k)
        for _ in range(per_client):
            n = int(rng.randint(2, 13))
            chain = [int(rng.randint(0, VOCAB))]
            for _ in range(n + 10):
                chain.append(successor(chain[-1]))
            prompt = np.asarray(chain[:n], np.int32)
            want = np.asarray(chain[n:n + 10], np.int32)
            try:
                out = srv(prompt, max_new_tokens=10,
                          temperature=0.0, timeout=60)
            except (serving.RejectedError,
                    serving.DeadlineExceededError):
                continue          # shed or expired under load: skip
            with lock:
                results.append((prompt, out, np.mean(out == want)))

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(args.clients)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.time() - t0

    st = srv.stats
    acc = float(np.mean([r[2] for r in results])) if results else 0.0
    if results:
        p, o, _ = results[0]
        print(f"sample: prompt {p.tolist()} -> {o.tolist()}")
    print(f"served {len(results)} generations in {dt:.2f}s "
          f"({st['tokens_out']} tokens, {st['decode_steps']} decode "
          f"steps, {st['prefills']} prefills)")
    print(f"cycle-continuation accuracy: {acc:.2f}")
    print(f"jit cache: {srv.jit_cache_count()} == census {srv.census()} "
          f"(0 traffic recompiles)")
    drained = srv.drain()
    print(f"drained={drained}, pages reclaimed "
          f"{srv.alloc.free_count()}/{srv.alloc.allocatable}")
    if acc < 0.5:
        print("WARNING: low continuation accuracy — train longer "
              "(--train-steps)")


if __name__ == "__main__":
    main()
