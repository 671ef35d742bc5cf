"""Symbol graph core: nodes, traversal, tracing to a pure JAX function.

Reference: NNVM ``Graph/Node/Symbol`` (``src/executor/graph_executor.h:33-35``)
and the pass pipeline (Gradient / InferShape / PlanMemory — SURVEY.md §3.1).

TPU-native position: the graph here is only a *frontend* expression DAG.  All
of NNVM's passes collapse into XLA:

- InferShape/InferType  → ``jax.eval_shape`` over the traced function
- Gradient              → ``jax.grad``/``jax.vjp`` of the traced function
- PlanMemory/inplace    → XLA buffer assignment + donated arguments
- PlaceDevice/group2ctx → pjit shardings from ``__ctx_group__`` attrs
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax

from ..ops.registry import Op

# per-op parameter/aux input declarations for auto-created variables
# (reference: each op's ListArguments/ListAuxiliaryStates)
OP_EXTRA_INPUTS: Dict[str, Tuple[Tuple[str, ...], Tuple[str, ...]]] = {
    # opname: ((learnable param inputs after data...), (aux inputs))
    "FullyConnected": (("weight", "bias"), ()),
    "Convolution": (("weight", "bias"), ()),
    "Deconvolution": (("weight", "bias"), ()),
    "BatchNorm": (("gamma", "beta"), ("moving_mean", "moving_var")),
    "LayerNorm": (("gamma", "beta"), ()),
    "InstanceNorm": (("gamma", "beta"), ()),
    "Embedding": (("weight",), ()),
    "RNN": (("parameters", "state", "state_cell"), ()),
    "LeakyReLU": (("gamma",), ()),
    # int8 serving twins (docs/quantization.md): act_scale rides from the
    # quantize node; weight/wscale are the offline-quantized variables
    "_tpumx_quantized_fc_int8": (("act_scale", "weight", "wscale", "bias"),
                                 ()),
    "_tpumx_quantized_conv_int8": (("act_scale", "weight", "wscale",
                                    "bias"), ()),
}

def attr_bool(v, default=False):
    """Boolean attr that may arrive stringly-typed ("False", "0", "true" —
    the reference frontend stringifies every attr); plain truthiness would
    read "False" as True and silently change the graph structure."""
    if v is None:
        return default
    if isinstance(v, str):
        return v.strip().lower() in ("1", "true", "yes")
    return bool(v)


# ops whose extra-input list depends on attrs
def _active_extra_inputs(opname: str, attrs: dict) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    params, aux = OP_EXTRA_INPUTS.get(opname, ((), ()))
    if opname in ("FullyConnected", "Convolution", "Deconvolution",
                  "_tpumx_quantized_fc_int8", "_tpumx_quantized_conv_int8") \
            and attr_bool(attrs.get("no_bias")):
        params = tuple(p for p in params if p != "bias")
    if opname == "LeakyReLU" and attrs.get("act_type", "leaky") != "prelu":
        params = ()
    if opname == "RNN":
        # the RNN op's own default mode is "lstm" (ops/rnn.py), so a missing
        # attr must keep the state_cell slot or the kernel runs an LSTM with
        # a silently-zero cell state
        if attrs.get("mode", "lstm") != "lstm":
            params = ("parameters", "state")
    return params, aux


class Node:
    """One graph node: a variable or an op application."""

    __slots__ = ("kind", "name", "op", "attrs", "inputs", "attr_dict", "_uid")

    _next_uid = [0]

    def __init__(self, kind: str, name: str, op: Optional[Op] = None,
                 attrs: Optional[dict] = None, inputs: Optional[List["SymbolEntry"]] = None,
                 attr_dict: Optional[dict] = None):
        self.kind = kind  # 'var' | 'op'
        self.name = name
        self.op = op
        self.attrs = attrs or {}
        self.inputs = inputs or []
        self.attr_dict = attr_dict or {}
        self._uid = Node._next_uid[0]
        Node._next_uid[0] += 1

    def num_outputs(self) -> int:
        if self.kind == "var":
            return 1
        return self.op.n_outputs(self.attrs)


class SymbolEntry:
    """(node, output_index) pair — an edge source in the DAG."""

    __slots__ = ("node", "index")

    def __init__(self, node: Node, index: int = 0):
        self.node = node
        self.index = index


def topo_order(entries: Sequence[SymbolEntry]) -> List[Node]:
    """Post-order DFS over the DAG, deduplicated (reference: nnvm DFSVisit,
    which is iterative for the same reason this is: a 1000+-op chain — a
    deeply unrolled RNN, a long residual stack — must not hit Python's
    recursion limit)."""
    seen = set()
    order: List[Node] = []
    stack: List[tuple] = []
    for e in entries:
        if id(e.node) in seen:
            continue
        seen.add(id(e.node))
        stack.append((e.node, 0))
        while stack:
            node, i = stack[-1]
            if i < len(node.inputs):
                stack[-1] = (node, i + 1)
                child = node.inputs[i].node
                if id(child) not in seen:
                    seen.add(id(child))
                    stack.append((child, 0))
            else:
                stack.pop()
                order.append(node)
    return order


def input_nodes(entries: Sequence[SymbolEntry], include_aux=True) -> List[Node]:
    """All variable nodes in traversal order."""
    out = []
    for n in topo_order(entries):
        if n.kind == "var":
            if not include_aux and n.attr_dict.get("__is_aux__"):
                continue
            out.append(n)
    return out


def eval_node(node: Node, ins: List[object], is_train: bool, rng_key=None,
              collect_aux: Optional[dict] = None) -> tuple:
    """Evaluate one op node over jax values (shared by whole-graph trace and
    the group2ctx segment executor)."""
    from ..ndarray.ndarray import _op_accepts_training

    kwargs = dict(node.attrs)
    op = node.op
    if op.rng:
        if rng_key is None:
            rng_key = jax.random.PRNGKey(0)
        kwargs["rng_key"] = jax.random.fold_in(rng_key, node._uid)
    if _op_accepts_training(op):
        kwargs["_training"] = is_train
    if op.name == "BatchNorm" and collect_aux is not None and is_train \
            and not attr_bool(kwargs.get("use_global_stats")):
        user_wants_stats = attr_bool(node.attrs.get("output_mean_var"))
        kwargs["output_mean_var"] = True
        y, mean, var = op.fn(*ins, **kwargs)
        aux_names = [e.node.name for e in node.inputs[-2:]]
        momentum = float(kwargs.get("momentum", 0.9))
        collect_aux[aux_names[0]] = momentum * ins[-2] + (1 - momentum) * mean
        collect_aux[aux_names[1]] = momentum * ins[-1] + (1 - momentum) * var
        # if the symbol itself declared output_mean_var, it has 3 outputs —
        # keep them or downstream indexing hits a 1-tuple
        return (y, mean, var) if user_wants_stats else (y,)
    out = op.fn(*ins, **kwargs)
    return tuple(out) if isinstance(out, (tuple, list)) else (out,)


def trace(entries: Sequence[SymbolEntry], env: Dict[str, object], is_train: bool,
          rng_key=None, collect_aux: Optional[dict] = None):
    """Evaluate the DAG over jax values.

    env: variable name -> jax value.  Random ops fold the node uid into
    rng_key.  When collect_aux is a dict and is_train, BatchNorm nodes place
    their (batch_mean, batch_var) under their aux variable names so the
    executor can update running stats functionally.
    """
    values: Dict[int, tuple] = {}

    for node in topo_order(entries):
        if node.kind == "var":
            if node.name not in env:
                raise ValueError(f"unbound variable {node.name!r}")
            values[id(node)] = (env[node.name],)
            continue
        ins = [values[id(e.node)][e.index] for e in node.inputs]
        # the device's name for this node's work: ``<operator>/<node name>``
        # in every operation's ``op_name`` (docs/observability.md "Device
        # scopes"); read while tracing only, the lowered program is the same
        with jax.named_scope(node.op.name), jax.named_scope(node.name):
            values[id(node)] = eval_node(node, ins, is_train, rng_key,
                                         collect_aux)

    return [values[id(e.node)][e.index] for e in entries]
