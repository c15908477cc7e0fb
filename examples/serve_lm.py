"""Serve a small model with batched requests.

Batch formation sorts requests by prompt length with the engine's pairs
sort (the paper's primitive in its serving role), then prefill + greedy
decode with a padded KV cache.

    PYTHONPATH=src python examples/serve_lm.py
"""

import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import registry
from repro.serve.engine import Request, ServeEngine


def main():
    cfg = registry.get_config("gemma3-4b", smoke=True)
    api = registry.get_model_api(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, api, max_len=128)

    rng = np.random.default_rng(0)
    lengths = [3, 21, 9, 33, 5, 14, 27, 8]
    reqs = [
        Request(i, rng.integers(0, cfg.vocab_size, ln).astype(np.int32),
                max_new_tokens=12)
        for i, ln in enumerate(lengths)
    ]
    ordered = eng.order_by_length(reqs)
    print("batch order after length sort:", [len(r.prompt) for r in ordered])
    out = eng.generate(reqs)
    for rid in sorted(out):
        print(f"request {rid} (prompt {lengths[rid]:2d} toks) -> {out[rid]}")


if __name__ == "__main__":
    main()
