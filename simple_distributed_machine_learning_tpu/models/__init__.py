"""Model zoo, expressed as pipeline stages (see ``parallel.pipeline.Stage``).

Scope per BASELINE.json configs: N-layer MLPs (2- and 4-stage pipelines),
LeNet with the reference's conv↔fc split, and a tiny GPT with GPipe
microbatching.
"""

from simple_distributed_machine_learning_tpu.models.beam import (  # noqa: F401
    make_beam_decoder,
)
from simple_distributed_machine_learning_tpu.models.gpt import (  # noqa: F401
    GPTConfig,
    decoder_from_pipeline,
    generate,
    make_cached_decoder,
    make_decoder,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.models.lenet import (  # noqa: F401
    make_lenet_stages,
)
from simple_distributed_machine_learning_tpu.models.pp_decode import (  # noqa: F401
    make_pp_decoder,
)
from simple_distributed_machine_learning_tpu.models.mlp import make_mlp_stages  # noqa: F401
