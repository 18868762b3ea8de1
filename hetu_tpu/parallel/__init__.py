from .mesh import (Mesh, DistState, DeviceGroup, make_mesh,
                   single_device_mesh, to_named_sharding, replicated)
from .dispatch import dispatch, DispatchOp
from .strategies import (Strategy, DataParallel, FSDP, MegatronLM,
                         ModelParallel4CNN, ExpertParallel)
from .pipeline import PipelineParallel, spmd_pipeline
from .hetpipe import HetPipeTrainer, DenseParamStore
from .context_parallel import (ring_attention, ulysses_attention,
                               ring_attention_shard, ulysses_attention_shard)
from . import collectives
from . import debug
from .search import (OptCNNSearch, FlexFlowSearch, GPipeSearch,
                     PipeDreamSearch, PipeOptSearch, SearchedStrategy,
                     partition_stages)
