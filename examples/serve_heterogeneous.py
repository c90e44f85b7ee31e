"""SMS-scheduled serving with a REAL model over a paged KV pool.

  PYTHONPATH=src python examples/serve_heterogeneous.py

Two clients — an interactive chat stream and a bulk tenant whose requests
share a prefix — are scheduled by the three SMS stages into a
continuous-batching engine running a tiny dense model with the Pallas
paged-attention kernel (interpret mode on CPU). Shared-prefix pages are
allocated once and ref-counted (stage-1 "row hits").
"""
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs.base import RunConfig, reduced
from repro.configs.registry import get_config
from repro.serving import paged_lm
from repro.serving.kv_cache import PagedAllocator
from repro.serving.scheduler import SMSScheduler
from repro.serving.types import Request

PAGE = 8
RUN = RunConfig(compute_dtype="float32")


def main():
    compile_cache.enable()
    cfg = reduced(get_config("qwen1.5-4b"), n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=256)
    params = jax.tree_util.tree_map(
        lambda x: x, __import__("repro.models.registry",
                                fromlist=["get_model"]).get_model(cfg).init(
        jax.random.PRNGKey(0)))
    alloc = PagedAllocator(n_pages=64, page_size=PAGE)
    sched = SMSScheduler(n_clients=2, sjf_prob=0.9, age_cap_ms=5.0)
    pools = paged_lm.init_pools(cfg, n_pages=64, page_size=PAGE)

    # client 0: 3 interactive requests; client 1: 4 bulk with shared prefix
    reqs = []
    rid = 0
    for i in range(3):
        r = Request(rid, 0, prefix_id=-(rid + 1), prompt_len=6, max_new=6,
                    arrival=float(i))
        r.shared_prefix_len = 0
        reqs.append(r)
        rid += 1
    for i in range(4):
        r = Request(rid, 1, prefix_id=42, prompt_len=2 * PAGE + 3, max_new=6,
                    arrival=0.0)
        r.shared_prefix_len = 2 * PAGE
        reqs.append(r)
        rid += 1
    for r in reqs:
        sched.enqueue(r, r.arrival)

    rng = np.random.RandomState(0)
    running = []   # (req, pages, tokens, pos)
    now, finished = 0.0, []
    while len(finished) < len(reqs):
        while len(running) < 4:
            req = sched.pop_admission(now)
            if req is None:
                break
            got = alloc.alloc_seq(req.prompt_len + req.max_new,
                                  req.prefix_id if req.prefix_id >= 0 else
                                  None, prefix_len=req.shared_prefix_len)
            assert got is not None
            pages, n_shared = got
            prompt = list(rng.randint(1, cfg.vocab_size, req.prompt_len))
            running.append([req, pages, prompt, 0])
            print(f"t={now:5.1f} admit r{req.rid} client{req.client} "
                  f"pages={pages[:4]}{'...' if len(pages) > 4 else ''} "
                  f"shared={n_shared}")
        # one decode step for every running sequence (prompt replay = chunked
        # prefill through the same paged step)
        B = len(running)
        tok = jnp.asarray([r[2][r[3]] if r[3] < len(r[2]) else r[2][-1]
                           for r in running], jnp.int32)
        pos = jnp.asarray([r[3] for r in running], jnp.int32)
        n_slots = max(len(r[1]) for r in running)
        pt = jnp.asarray([r[1] + [r[1][-1]] * (n_slots - len(r[1]))
                          for r in running], jnp.int32)
        logits, new_pools = paged_lm.paged_decode_step(
            params, cfg, RUN, pools, tok, pos, pt, page_size=PAGE)
        pools = new_pools
        nxt = np.asarray(jnp.argmax(logits, -1))
        done = []
        for i, r in enumerate(running):
            r[3] += 1
            if r[3] >= len(r[2]):                  # generating
                r[2].append(int(nxt[i]))
            if r[3] >= r[0].prompt_len + r[0].max_new:
                done.append(r)
        for r in done:
            running.remove(r)
            alloc.free_seq(r[1])
            sched.on_finish(r[0])
            finished.append(r[0])
            gen = r[2][r[0].prompt_len:]
            print(f"t={now:5.1f} done  r{r[0].rid} client{r[0].client} "
                  f"generated={gen}")
        now += 1.0
    print(f"\nall {len(finished)} requests served; "
          f"page utilization returned to {alloc.utilization():.0%} "
          f"(prefix pages stay pinned)")


if __name__ == "__main__":
    main()
