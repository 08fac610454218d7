"""End-to-end serving driver (deliverable b): a Poisson request trace
through the SpecOffload serving path — prefill on admission, dual-batch
interleaved decode with speculative verification, and the
continuous-batching scheduler (EOS retirement + mid-flight admission).

    PYTHONPATH=src python examples/serve_spec_offload.py [--arch mixtral-8x7b]

Uses the reduced config of the chosen architecture so it runs on CPU; the
pipeline structure (interleaved batches, rollback, slot scheduler) is the
production one.  ``python -m repro.launch.serve --plan`` prints the
placement plan and ParaSpec policy for a configuration.
"""
import argparse

import numpy as np

from repro.configs import get_config
from repro.configs.base import MISTRAL_7B
from repro.data.pipeline import synthetic_dataset
from repro.serving.engine import (SchedulerConfig, ServingEngine,
                                  latency_percentiles)
from repro.serving.trace import poisson_requests
from repro.sim.hardware import ENV1

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="mixtral-8x7b")
ap.add_argument("--requests", type=int, default=8)
ap.add_argument("--gen", type=int, default=12)
ap.add_argument("--rate", type=float, default=2.0, help="req/s (Poisson)")
args = ap.parse_args()

tcfg = get_config(args.arch).reduced(d_model=128)
dcfg = MISTRAL_7B.reduced(d_model=64, vocab=tcfg.vocab_size)

print(f"target: {tcfg.name} (reduced) | draft: {dcfg.name}")
eng = ServingEngine(tcfg, dcfg, ENV1,
                    config=SchedulerConfig(max_batch=2, n_cand=3))
eng.init_from_seed(0)

ds = synthetic_dataset("samsum", n_prompts=args.requests,
                       vocab=tcfg.vocab_size)
rng = np.random.default_rng(1)
gens = rng.integers(max(2, args.gen // 2), args.gen + 1, args.requests)
reqs = poisson_requests([p[:24] for p in ds.prompts], gens.tolist(),
                        args.rate)
for r in reqs:
    eng.submit(r)

done = eng.run()
st = eng.stats()
toks = sum(len(r.result) for r in done)
print(f"\nserved {len(done)} requests / {toks} tokens in "
      f"{st['wall_s']:.1f}s ({eng.throughput(done):.2f} tok/s)")
print(f"occupancy={st['mean_occupancy']:.2f}, rounds={st['rounds']}, "
      f"fused compiles={st['fused_compiles']}")
print("ttft:", latency_percentiles(done, "ttft_s"))
print("e2e: ", latency_percentiles(done, "latency_s"))
print("first result tokens:", np.asarray(done[0].result).tolist())
