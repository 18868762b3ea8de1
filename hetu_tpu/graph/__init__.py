from .node import (Op, PlaceholderOp, VariableOp, find_topo_sort,
                   graph_variables, graph_placeholders, stage,
                   current_stage, name_scope, scoped_init, remat, scope,
                   scopes, named_scope)
from .trace import TraceContext, evaluate
from .autodiff import gradients
from .executor import Executor, SubExecutor
from .checkpoint import save_sharded, load_sharded
