"""Quickstart: serve internlm2_1_8b through the full control plane
(real compute, published widths, bf16; meant for a TPU chip).

Scenario arrivals enter via the Gateway, ESG_1Q plans batch sizes from a
measured profile lattice, and every dispatched batch runs real Pallas
prefill + scalar-prefetch decode via the compile-cached RealExecutor.

  PYTHONPATH=src python examples/quickstart.py
"""
from repro.configs.registry import get_config
from repro.launch.chip import use_compile_cache
from repro.launch.serve import serve_real
from repro.serving.executor import RealExecutor

if __name__ == "__main__":
    use_compile_cache()
    ex = RealExecutor(get_config("internlm2_1_8b"), batch_lattice=(1, 2, 4),
                      quotas=(1.0,))
    out = serve_real(ex, n_requests=24, reps=1)
    ex.shutdown()
    st = out["executor"]
    print(f"served {out['n_requests']} requests on {out['device']['kind']}: "
          f"executed={st['executed']} batches, "
          f"compile-cache hit rate={st['post_warmup_hit_rate']:.2f}, "
          f"predicted-vs-measured err={out['mean_abs_err']:.1%}")
