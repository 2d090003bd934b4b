"""paddle_tpu: a TPU-native deep-learning framework.

Capability parity target: PaddlePaddle ~v1.7 (static "fluid" graphs +
imperative dygraph + distributed training); architecture: JAX/XLA/Pallas.
See SURVEY.md at the repo root for the reference layer map this package
rebuilds.

Top-level namespace mirrors the reference's `paddle.fluid` surface:

    import paddle_tpu as fluid
    x = fluid.data("x", [None, 784])
    y = fluid.layers.fc(x, 10)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
"""

from . import compile_cache

compile_cache.install()   # before anything below can compile

from . import flags
from .flags import set_flags, get_flags

from .core import (
    CPUPlace,
    TPUPlace,
    CUDAPlace,
    CUDAPinnedPlace,
    default_place,
    is_compiled_with_tpu,
    device_count,
)

from . import ops  # registers all op kernels
from .framework import (
    Program,
    Variable,
    Parameter,
    default_main_program,
    default_startup_program,
    program_guard,
    name_scope,
    data,
    Executor,
    CompiledProgram,
    BuildStrategy,
    ExecutionStrategy,
    Scope,
    global_scope,
    scope_guard,
    append_backward,
    gradients,
    ParamAttr,
    cpu_places,
    cuda_places,
    cuda_pinned_places,
    in_dygraph_mode,
    is_compiled_with_cuda,
    load_op_library,
    require_version,
    device_guard,
)

# top-level fluid module paths (richer than the framework internals:
# initializer adds init_on_cpu, unique_name adds switch)
from . import initializer
from . import unique_name
from . import backward

from . import analysis  # static Program verifier (FLAGS_static_check)
from . import layers
from . import nets
from . import debugger
from . import average
from . import install_check
from . import model_stat
from . import contrib
from . import (communicator, compiler, data_feeder, evaluator,  # noqa: F401
               executor, input, lod_tensor, log_helper, param_attr,
               parallel_executor)
from .parallel_executor import ParallelExecutor  # noqa: F401
from . import compat  # noqa: F401
from . import incubate  # noqa: F401
from .reader import batch  # noqa: F401
from . import dygraph_grad_clip  # noqa: F401
from .param_attr import WeightNormParamAttr  # noqa: F401
from . import sysconfig
from . import utils
from .lod import (LoDTensor, create_lod_tensor,
                  create_random_int_lodtensor)
from . import optimizer
from . import regularizer
from . import clip
from . import io
from . import reader
from . import dataset
from . import metrics
from . import profiler
from . import monitor
from . import resilience
from . import nn
from . import dygraph
from . import distributed
from . import amp
from . import jit
from . import models
from . import slim
from . import checkpoint
from . import inference
from . import transpiler
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig

from .reader import DataLoader
from .version import full_version as __version__

__all__ = [
    "flags", "set_flags", "get_flags",
    "CPUPlace", "TPUPlace", "CUDAPlace", "CUDAPinnedPlace",
    "default_place", "is_compiled_with_tpu", "device_count",
    "ops", "Program", "Variable", "Parameter",
    "default_main_program", "default_startup_program", "program_guard",
    "name_scope", "data", "Executor", "Scope", "global_scope",
    "scope_guard", "append_backward", "gradients", "ParamAttr",
    "initializer", "unique_name", "backward", "layers", "optimizer",
    "regularizer", "clip", "io", "reader", "dataset", "metrics",
    "profiler", "monitor", "nn", "dygraph", "distributed", "amp", "jit",
    "models",
    "contrib",
    "DataLoader",
]
