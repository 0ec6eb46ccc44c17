#!/usr/bin/env python3
"""``sweep.py`` for a hybrid serve cell (traffic driver ``serve_hybrid``):
the same sweep, with the hybrid driver's set-up (its configuration,
weights and warm-up) in place of the dense decoder's.

    python3 chipbench/sweep_hybrid.py \
        --workload serve.granite-4.0-h-micro.rag --rates 2,4,6 \
        --seconds 30 --seed 5
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import sweep  # noqa: E402
from chipbench.drivers import serve, serve_hybrid  # noqa: E402

if __name__ == "__main__":
    serve.setup = serve_hybrid.setup
    sys.exit(sweep.main())
