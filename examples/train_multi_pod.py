"""End-to-end driver: rollup-FL training of an LM across the production mesh.

This is the launch/train.py entry exercised end-to-end: on real hardware it
runs the full pipeline on the 16x16 (or 2x16x16) mesh; on this CPU container
pass --host-mesh to run the REAL sharded code path on a 1x1 mesh, or use
launch/dryrun.py for the 256/512-chip compile proof.

Usage:
    PYTHONPATH=src python examples/train_multi_pod.py \
        --arch qwen2-0.5b --rounds 3 --local-steps 2 --host-mesh --reduced
"""

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main


if __name__ == "__main__":
    enable_compile_cache()
    train_main()
