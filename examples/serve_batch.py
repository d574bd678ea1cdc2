"""Batched serving example: continuous-batching engine over a small
decoder, several concurrent requests with different prompt lengths.

    PYTHONPATH=src python examples/serve_batch.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import reduced
from repro.models import transformer as TF
from repro.serve.engine import Request, ServeEngine


def main():
    cfg = dataclasses.replace(reduced(get_config("glm4-9b")),
                              max_seq_len=256)
    key = jax.random.PRNGKey(0)
    params = TF.init_params(key, cfg)
    engine = ServeEngine(params, cfg, batch_slots=4, max_len=128,
                         dtype=jnp.float32)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        8 + 4 * i).astype(np.int32),
                    max_new_tokens=12)
            for i in range(6)]
    for r in reqs:
        engine.submit(r)

    ticks = 0
    while engine.waiting or any(engine.active):
        engine.step()
        ticks += 1
    # long-running step() loops must drain periodically so retired
    # requests do not accumulate in the engine
    done = {r.rid: r for r in engine.drain_retired()}
    for rid in sorted(done):
        r = done[rid]
        print(f"req {r.rid}: prompt[{len(r.prompt)}] -> {r.out}")
    print(f"served {len(done)} requests in {ticks} engine ticks "
          f"(batched decode, {engine.slots} slots)")


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    main()
