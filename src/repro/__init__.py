"""repro — Byzantine Gradient Descent (Chen, Su, Xu 2017) as a production
multi-pod JAX/TPU training & serving framework.

Subpackages:
    core        the paper's algorithm (geomed, aggregators, attacks, steps)
    models      the 10-assigned-architecture model zoo
    kernels     the fused GMoM round, a Pallas TPU kernel
    data        synthetic deterministic pipelines (+ the paper's linreg)
    optim       SGD (paper) / AdamW
    checkpoint  msgpack pytree checkpoints
    configs     architecture + input-shape registry
    launch      meshes, sharding rules, dry-run, train/serve drivers
    roofline    compiled-HLO roofline analysis
"""

__version__ = "1.0.0"
