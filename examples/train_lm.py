"""Train a small LM end-to-end on CPU (reduced config of an assigned arch)
with the full substrate: data pipeline, AdamW, checkpointing, straggler
monitor. The full-size configs are exercised via the multi-pod dry-run
(repro.launch.dryrun); this example proves the training loop itself.

    PYTHONPATH=src python examples/train_lm.py --arch smollm-360m --steps 200
"""
import sys

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main

if __name__ == "__main__":
    enable_compile_cache()
    if len(sys.argv) == 1:
        sys.argv += ["--arch", "smollm-360m", "--steps", "200", "--batch", "8",
                     "--seq", "128"]
    main()
