"""repro.serve -- production serving harness on the plan engine.

``Server`` is the decode loop: it holds persistent compiled prefill/decode
functions, AOT-warms a declared (batch, seq) bucket grid (filling the plan
cache with each bucket's ``SchedulePlan``s; ``Server.warmup()`` returns
the per-bucket report), and routes incoming request batches to the
nearest warm bucket via left-padding + position offsets.  See
``repro.runtime.serve`` for a bucket's compiled programs and
``benchmarks/serve_sweep.py`` for the config-matrix latency sweep.
"""
from .buckets import Bucket, as_bucket, bucket_grid, route
from .server import DEFAULT_BUCKETS, Server, ServeResult

__all__ = [
    "Bucket", "as_bucket", "bucket_grid", "route",
    "Server", "ServeResult", "DEFAULT_BUCKETS",
]
