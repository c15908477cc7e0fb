"""repro: OTIS Hyper Hexa-Cell parallel Quick Sort as a multi-pod JAX framework.

Layers: core (the paper's algorithm, the sort engine and distributed
sorts; local sorts are XLA's), models (10 assigned architectures),
configs, data, optim, train, serve, ckpt, runtime (fault tolerance, PP,
collectives), launch (mesh/dryrun/train/serve), roofline.

See DESIGN.md (architecture contract), README.md (map + quickstart), and
benchmarks/README.md (paper figure/table coverage).
"""

__version__ = "1.0.0"
