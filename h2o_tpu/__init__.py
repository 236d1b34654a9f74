"""h2o_tpu — a TPU-native distributed ML platform with the capabilities of H2O-3.

From-scratch JAX/XLA design (see SURVEY.md for the blueprint): frames are
row-sharded JAX arrays over a device mesh, the MRTask compute driver is
shard_map + XLA collectives, and algorithms (GBM/DRF, GLM, KMeans, PCA, ...) run
their hot loops on the MXU.
"""

from .backend.jobs import Job, JobCancelled, JobTimeoutError
from .backend.kvstore import STORE, Keyed, KVStore, make_key
from .frame.frame import Frame
from .frame.vec import Vec
from .parallel import mesh
from .parallel.mesh import default_mesh, make_mesh, use_mesh
from .parallel.mrtask import mr_map, mr_reduce

__version__ = "0.1.0"


def resume_training(recovery_dir: str):
    """Restart a killed training job from its auto-recovery dir (lazy
    import — the models package is heavy and most sessions never resume)."""
    from .models.model_base import resume_training as _resume

    return _resume(recovery_dir)


__all__ = [
    "Frame", "Vec", "Job", "JobCancelled", "JobTimeoutError", "STORE",
    "Keyed", "KVStore",
    "make_key", "mesh", "default_mesh", "make_mesh", "use_mesh",
    "mr_map", "mr_reduce", "resume_training", "__version__",
]
