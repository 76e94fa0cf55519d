"""Long-context serving with GEAR: grow a cache past what FP16 would allow
under the same byte budget, and watch compression events stream.

    PYTHONPATH=src python examples/longcontext_gear.py
"""
import dataclasses

import jax
import numpy as np

from repro.configs import smoke_config
from repro.core.policy import named_policy
from repro.core import metrics
from repro.models.model import build_model
from repro.serving.engine import Engine, EngineConfig
from repro.serving.scheduler import Request, Scheduler


def main():
    cfg = smoke_config("gemma3-12b")  # local:global pattern — window + GEAR caches
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pol = dataclasses.replace(named_policy("gear_kivi2"), buffer_size=16, group=16)

    eng = Engine(model, params, EngineConfig(batch=1, capacity=512, policy=pol))
    sched = Scheduler(eng)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (64,), 0,
                                           cfg.vocab_size))
    sched.submit(Request(rid=0, tokens=prompt, max_new_tokens=128))
    (res,) = sched.run_continuous()
    # the cache tree's size is fixed when the engine is built: a fresh tree
    # holds exactly the bytes of the one the request was served from
    nbytes = Engine.cache_nbytes(eng.init_caches())
    print(f"generated {len(res.tokens)} tokens; cache {nbytes/1e6:.2f} MB")

    frac = metrics.kv_size_fraction(pol, 512, cfg.num_kv_heads * cfg.head_dim,
                                    num_heads=cfg.num_kv_heads, head_dim=cfg.head_dim)
    print(f"analytic compressed size: {100*frac:.1f}% of FP16 "
          f"→ {1/frac:.1f}× longer context at equal HBM")


if __name__ == "__main__":
    main()
