from theanompi_tpu.runtime.mesh import (  # noqa: F401
    init_distributed,
    make_mesh,
    replicated_sharding,
    batch_sharding,
    num_devices,
)
from theanompi_tpu.runtime.config import Config  # noqa: F401
from theanompi_tpu.runtime.recorder import Recorder  # noqa: F401
