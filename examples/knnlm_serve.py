"""End-to-end retrieval-augmented serving: build a DB-LSH datastore from
an LM's own hidden states, then serve batched requests through the
continuous-batching engine with kNN-LM interpolation.

    PYTHONPATH=src python examples/knnlm_serve.py
"""

import jax
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import SyntheticTokens, make_batch_fn
from repro.jit_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serve import Request, RetrievalLM, ServeEngine, build_datastore


def main():
    cfg = get_config("yi-9b").scaled(
        n_layers=4, d_model=256, n_heads=8, n_kv_heads=4, d_ff=768,
        head_dim=32, vocab_size=8192, dtype="float32",
    )
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    # datastore: teacher-forced pass over a small corpus
    src = SyntheticTokens(cfg.vocab_size, 64, 4, seed=7)
    batches = [make_batch_fn(src)(s) for s in range(8)]
    ds = build_datastore(model, params, batches, jax.random.key(1),
                         t=64, k=8, lam=0.3)
    print(f"datastore: {ds.index.n} keys, L={ds.index.params.L} tables")

    rlm = RetrievalLM(model, ds, r0=1.0, steps=5)
    eng = ServeEngine(model, params, slots=4, cache_len=128, retrieval=rlm)

    rng = np.random.default_rng(0)
    reqs = [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, size=8).astype(np.int32),
                max_new_tokens=16, temperature=0.8 if i % 2 else 0.0)
        for i in range(8)
    ]
    for r in reqs:
        eng.submit(r)
    steps = eng.run()
    print(f"served {len(reqs)} requests in {steps} engine steps "
          f"(continuous batching over {eng.slots} slots)")
    for r in reqs[:3]:
        print(f"  req {r.uid}: {r.output}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
