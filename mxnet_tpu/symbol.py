"""Symbol — declarative graph construction.

Re-design of the reference's nnvm::Symbol + python/mxnet/symbol.py (1,424
LoC).  A Symbol is a list of (node, output_index) heads over a DAG of
``_Node`` objects.  Graph compilation happens at bind time: the executor
traces the DAG into one JAX function and jits it — the NNVM pass pipeline
(InferShape/InferType/PlanMemory/bulk segmentation,
src/executor/graph_executor.cc:372-690) collapses into XLA's compiler.

API parity: Variable/Group/compose, list_arguments/outputs/auxiliary_states,
infer_shape(_partial), infer_type, attr scoping, save/load JSON
(format-compatible with the reference's graph JSON), bind/simple_bind, grad.
"""
from __future__ import annotations

import builtins
import json

import numpy as np

from . import attribute, name as _name_mod
from .base import MXNetError, attr_to_string, parse_attr_value
from .ops.registry import OP_REGISTRY, get_op

__all__ = ["Symbol", "Variable", "Group", "load", "load_json", "var"]

# attrs that belong to the framework, not to the op's kernel
_RESERVED_ATTRS = frozenset((
    "ctx_group", "lr_mult", "wd_mult", "force_mirroring", "__shape__",
    "__dtype__", "__init__",
))


class _Node(object):
    __slots__ = ("op", "name", "attrs", "inputs", "_uid")
    _uid_counter = [0]

    def __init__(self, op, name, attrs=None, inputs=None):
        self.op = op          # OpDef or None for variables
        self.name = name
        self.attrs = dict(attrs) if attrs else {}
        self.inputs = list(inputs) if inputs else []  # [(node, out_idx)]
        _Node._uid_counter[0] += 1
        self._uid = _Node._uid_counter[0]

    @property
    def is_variable(self):
        return self.op is None

    def op_attrs(self):
        """Attrs passed to the op function (reserved/meta attrs stripped)."""
        return {k: v for k, v in self.attrs.items()
                if k not in _RESERVED_ATTRS and not k.startswith("__")}

    def num_outputs(self):
        if self.is_variable:
            return 1
        return self.op.get_num_outputs(self.op.normalize_attrs(self.op_attrs()))


def _topo_sort(heads):
    """Post-order DFS over the DAG."""
    visited = set()
    order = []
    stack = [(h, False) for h in reversed(heads)]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in visited:
            continue
        visited.add(id(node))
        stack.append((node, True))
        for src, _idx in reversed(node.inputs):
            if id(src) not in visited:
                stack.append((src, False))
    return order


class Symbol(object):
    __slots__ = ("_outputs",)

    def __init__(self, outputs):
        self._outputs = list(outputs)  # [(node, out_idx)]

    # -- introspection ----------------------------------------------------
    def _nodes(self):
        return _topo_sort([n for n, _ in self._outputs])

    def _aux_var_names(self):
        """Variable names that feed aux-state slots of ops (the NNVM
        FMutateInputs analog)."""
        aux = set()
        for node in self._nodes():
            if node.is_variable:
                continue
            attrs = node.op.normalize_attrs(node.op_attrs())
            n_in = len(node.op.get_input_names(attrs))
            aux_names = node.op.get_aux_names(attrs)
            for k, (src, _idx) in enumerate(node.inputs):
                if k >= n_in and k < n_in + len(aux_names) and src.is_variable:
                    aux.add(src.name)
        return aux

    def list_arguments(self):
        aux = self._aux_var_names()
        out, seen = [], set()
        for node in self._nodes():
            if node.is_variable and node.name not in aux and node.name not in seen:
                seen.add(node.name)
                out.append(node.name)
        return out

    def list_auxiliary_states(self):
        aux = self._aux_var_names()
        out, seen = [], set()
        for node in self._nodes():
            if node.is_variable and node.name in aux and node.name not in seen:
                seen.add(node.name)
                out.append(node.name)
        return out

    def list_outputs(self):
        names = []
        for node, idx in self._outputs:
            if node.is_variable:
                names.append(node.name)
                continue
            attrs = node.op.normalize_attrs(node.op_attrs())
            out_names = node.op.get_output_names(attrs)
            if node.num_outputs() == 1:
                names.append(node.name + "_output")
            else:
                names.append("%s_%s" % (node.name, out_names[idx]))
        return names

    @property
    def name(self):
        if len(self._outputs) == 1:
            return self._outputs[0][0].name
        return None

    def attr(self, key):
        if len(self._outputs) == 1:
            v = self._outputs[0][0].attrs.get(key)
            return attr_to_string(v) if v is not None else None
        return None

    def list_attr(self):
        if len(self._outputs) == 1:
            return {k: attr_to_string(v)
                    for k, v in self._outputs[0][0].attrs.items()}
        return {}

    def attr_dict(self):
        out = {}
        for node in self._nodes():
            if node.attrs:
                out[node.name] = {k: attr_to_string(v)
                                  for k, v in node.attrs.items()}
        return out

    def _set_attr(self, **kwargs):
        for node, _ in self._outputs:
            node.attrs.update(kwargs)

    def get_internals(self):
        outs = []
        for node in self._nodes():
            for i in range(node.num_outputs()):
                outs.append((node, i))
        return Symbol(outs)

    def get_children(self):
        if len(self._outputs) != 1:
            return None
        node = self._outputs[0][0]
        if not node.inputs:
            return None
        return Symbol(list(node.inputs))

    def __getitem__(self, index):
        if isinstance(index, str):
            names = self.list_outputs()
            if index not in names:
                raise MXNetError("cannot find output %r; outputs=%s" % (index, names))
            index = names.index(index)
        if isinstance(index, builtins.slice):
            return Symbol(self._outputs[index])
        return Symbol([self._outputs[index]])

    def __len__(self):
        return len(self._outputs)

    def __iter__(self):
        for i in range(len(self._outputs)):
            yield self[i]

    def __repr__(self):
        if len(self._outputs) == 1:
            return "<Symbol %s>" % self._outputs[0][0].name
        return "<Symbol group [%s]>" % ", ".join(self.list_outputs())

    # -- composition ------------------------------------------------------
    def __call__(self, **kwargs):
        """Compose: replace variables by other symbols (symbol.py __call__)."""
        mapping = {}
        for k, v in kwargs.items():
            if not isinstance(v, Symbol):
                raise TypeError("compose expects Symbols")
            mapping[k] = v._outputs[0]
        memo = {}

        def rewrite_pair(node, idx):
            if node.is_variable and node.name in mapping:
                return mapping[node.name]
            if id(node) not in memo:
                new = _Node(node.op, node.name, node.attrs, [])
                memo[id(node)] = new
                new.inputs = [rewrite_pair(s, i) for s, i in node.inputs]
            return (memo[id(node)], idx)

        return Symbol([rewrite_pair(n, i) for n, i in self._outputs])

    # -- arithmetic -------------------------------------------------------
    def _binary(self, other, op_name, scalar_op, reverse=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return _create(op_name, [a, b], {})
        if isinstance(other, (int, float, np.number)):
            return _create(scalar_op, [self], {"scalar": float(other)})
        return NotImplemented

    def __add__(self, o):
        return self._binary(o, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binary(o, "elemwise_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __div__(self, o):
        return self._binary(o, "elemwise_div", "_div_scalar")

    __truediv__ = __div__

    def __rdiv__(self, o):
        return self._binary(o, "elemwise_div", "_rdiv_scalar", reverse=True)

    __rtruediv__ = __rdiv__

    def __pow__(self, o):
        return self._binary(o, "_power", "_power_scalar")

    def __neg__(self):
        return _create("_mul_scalar", [self], {"scalar": -1.0})

    def __eq__(self, o):
        if isinstance(o, (Symbol, int, float)):
            return self._binary(o, "broadcast_equal", "_equal_scalar")
        return NotImplemented

    def __ne__(self, o):
        if isinstance(o, (Symbol, int, float)):
            return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")
        return NotImplemented

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # -- shape / type inference ------------------------------------------
    def infer_shape(self, *args, **kwargs):
        arg_shapes, out_shapes, aux_shapes = self.infer_shape_partial(*args, **kwargs)
        if arg_shapes is not None and any(s is None for s in arg_shapes):
            unknown = [n for n, s in zip(self.list_arguments(), arg_shapes)
                       if s is None]
            raise MXNetError(
                "InferShape incomplete: cannot infer shapes of %s" % unknown)
        return arg_shapes, out_shapes, aux_shapes

    def infer_shape_partial(self, *args, **kwargs):
        if args:
            kwargs = dict(zip(self.list_arguments(), args))
            kwargs = {k: v for k, v in kwargs.items() if v is not None}
        known = {k: tuple(v) for k, v in kwargs.items() if v is not None}
        shapes = {}   # id(node) -> list of output shapes
        for node in self._nodes():
            if node.is_variable:
                s = known.get(node.name)
                if s is None and "__shape__" in node.attrs:
                    s = tuple(parse_attr_value(node.attrs["__shape__"]))
                shapes[id(node)] = [tuple(s) if s is not None else None]
                continue
            attrs = node.op.normalize_attrs(node.op_attrs())
            in_shapes = [shapes[id(src)][idx] for src, idx in node.inputs]
            new_in, out_sh = _infer_node(node, attrs, in_shapes)
            # back-fill variable shapes learned by the op's shape function
            for (src, idx), s in zip(node.inputs, new_in):
                if s is not None and src.is_variable and shapes[id(src)][0] is None:
                    shapes[id(src)][0] = tuple(s)
                    known[src.name] = tuple(s)
            shapes[id(node)] = list(out_sh)
        args_order = self.list_arguments()
        aux_order = self.list_auxiliary_states()
        by_name = {}
        for node in self._nodes():
            if node.is_variable:
                by_name[node.name] = shapes[id(node)][0]
        arg_shapes = [by_name.get(n) for n in args_order]
        aux_shapes = [by_name.get(n) for n in aux_order]
        out_shapes = [shapes[id(n)][i] for n, i in self._outputs]
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        """Basic dtype inference: float32 default; honors explicit hints and
        ``Variable(dtype=...)`` declarations (stored as __dtype__ attr)."""
        if args:
            kwargs = dict(zip(self.list_arguments(), args))
        dt = {k: np.dtype(v) for k, v in kwargs.items() if v is not None}
        for node in self._nodes():
            if node.is_variable and "__dtype__" in node.attrs:
                dt.setdefault(node.name, np.dtype(str(node.attrs["__dtype__"])))
        arg_types = [np.dtype(dt.get(n, np.float32)).type
                     for n in self.list_arguments()]
        aux_types = [np.float32 for _ in self.list_auxiliary_states()]
        out_types = [np.float32 for _ in self._outputs]
        return arg_types, out_types, aux_types

    # -- serialization ----------------------------------------------------
    def tojson(self):
        nodes = self._nodes()
        nid = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        arg_nodes = []
        for i, node in enumerate(nodes):
            if node.is_variable:
                arg_nodes.append(i)
            jnodes.append({
                "op": "null" if node.is_variable else node.op.name,
                "name": node.name,
                "attrs": {k: attr_to_string(v) for k, v in node.attrs.items()},
                "inputs": [[nid[id(s)], idx, 0] for s, idx in node.inputs],
            })
        heads = [[nid[id(n)], idx, 0] for n, idx in self._outputs]
        return json.dumps({
            "nodes": jnodes, "arg_nodes": arg_nodes, "heads": heads,
            "attrs": {"mxnet_tpu_version": "0.1.0"},
        }, indent=2)

    def save(self, fname):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # -- binding ----------------------------------------------------------
    def simple_bind(self, ctx, grad_req="write", type_dict=None, group2ctx=None,
                    shared_exec=None, **kwargs):
        from .executor import Executor
        return Executor._simple_bind(self, ctx, grad_req=grad_req,
                                     type_dict=type_dict, group2ctx=group2ctx,
                                     shared_exec=shared_exec, shapes=kwargs)

    def bind(self, ctx, args, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        from .executor import Executor
        return Executor(self, ctx, args, args_grad=args_grad,
                        grad_req=grad_req, aux_states=aux_states,
                        group2ctx=group2ctx, shared_exec=shared_exec)

    def grad(self, wrt):
        raise NotImplementedError(
            "Symbol.grad: use bind(...).backward() or autograd")

    # -- eval convenience -------------------------------------------------
    def eval(self, ctx=None, **kwargs):
        from .context import current_context
        ctx = ctx or current_context()
        ex = self.bind(ctx, kwargs)
        return ex.forward()


def _infer_node(node, attrs, in_shapes):
    """Shape inference for one node: custom fn, else jax.eval_shape fallback."""
    op = node.op
    if op.infer_shape is not None:
        new_in, out_sh, _aux = op.infer_shape(attrs, in_shapes)
        # custom infers cover declared inputs; aux inputs trail
        n_declared = len(new_in)
        full_in = list(new_in) + list(in_shapes[n_declared:])
        if _aux:
            n_in = len(op.get_input_names(attrs))
            for k, s in enumerate(_aux):
                if n_in + k < len(full_in) and full_in[n_in + k] is None:
                    full_in[n_in + k] = s
        return full_in, out_sh
    if any(s is None for s in in_shapes):
        return in_shapes, [None] * op.get_num_outputs(attrs)
    import jax
    import jax.numpy as jnp
    from .executor import _filter_attrs

    call_attrs = _filter_attrs(op, attrs)
    structs = [jax.ShapeDtypeStruct(s, jnp.float32) for s in in_shapes]
    kw = {}
    if op.needs_is_train:
        kw["is_train"] = False
    if op.needs_rng:
        kw["rng"] = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def f(*xs):
        return op.fn(*xs, **call_attrs, **kw)
    try:
        if op.needs_rng:
            kwr = dict(kw)
            kwr.pop("rng")

            def f2(rng, *xs):
                return op.fn(*xs, rng=rng, **call_attrs, **kwr)
            out = jax.eval_shape(f2, jax.ShapeDtypeStruct((2,), jnp.uint32),
                                 *structs)
        else:
            out = jax.eval_shape(f, *structs)
    except Exception as e:
        raise MXNetError("InferShape failed for op %s(%s): %s"
                         % (op.name, node.name, e)) from e
    if not isinstance(out, (tuple, list)):
        out = (out,)
    n_out = op.get_num_outputs(attrs)
    return in_shapes, [tuple(o.shape) for o in out][:n_out]


# ---------------------------------------------------------------------------
# construction helpers
# ---------------------------------------------------------------------------

def Variable(name, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, **kwargs):
    """Create a variable symbol (symbol.py Variable)."""
    attrs = attribute.current().get(attr)
    if shape is not None:
        attrs["__shape__"] = str(tuple(shape))
    if dtype is not None:
        attrs["__dtype__"] = str(np.dtype(dtype))
    if lr_mult is not None:
        attrs["lr_mult"] = str(lr_mult)
    if wd_mult is not None:
        attrs["wd_mult"] = str(wd_mult)
    if init is not None:
        attrs["__init__"] = init if isinstance(init, str) else init.dumps()
    attrs.update({k: str(v) for k, v in kwargs.items()})
    return Symbol([(_Node(None, name, attrs), 0)])


var = Variable


def Group(symbols):
    outs = []
    for s in symbols:
        outs.extend(s._outputs)
    return Symbol(outs)


def _create(op_name, input_syms, attrs, name=None, extra_attr=None,
            named_inputs=None):
    """Create an op node.  ``input_syms`` are positional inputs (used for
    variadic ops and operator sugar); ``named_inputs`` maps input-name ->
    Symbol.  Missing parameter/aux inputs are auto-created as Variables
    named ``{node}_{input}`` — the reference's auto-created weight/bias/aux
    variables (python/mxnet/symbol.py compose)."""
    op = get_op(op_name)
    op.validate_attrs(attrs, where="symbol")
    norm = op.normalize_attrs(attrs)
    hint = op.name.lstrip("_").lower()
    node_name = _name_mod.current().get(name, hint)
    node_attrs = dict(attrs)
    if extra_attr:
        node_attrs.update(extra_attr)
    scope_attrs = attribute.current().get(None)
    for k, v in scope_attrs.items():
        node_attrs.setdefault(k, v)

    def head(s):
        if len(s._outputs) != 1:
            raise MXNetError("op %s input must be single-output symbol" % op_name)
        return s._outputs[0]

    in_names = op.get_input_names(norm)
    aux_names = op.get_aux_names(norm)
    if op.variable_inputs:
        inputs = [head(s) for s in input_syms]
        # some variadic ops still declare named parameter inputs beyond the
        # user-supplied ones (UpSampling bilinear's weight) — auto-create them
        for nm in list(op.get_input_names(norm))[len(inputs):]:
            inputs.append(Variable("%s_%s" % (node_name, nm))._outputs[0])
    else:
        by_name = dict(named_inputs or {})
        for nm, s in zip(in_names, input_syms):
            if nm in by_name:
                raise MXNetError(
                    "op %s: input %r given both positionally and by keyword"
                    % (op_name, nm))
            by_name[nm] = s
        unknown = set(by_name) - set(in_names) - set(aux_names)
        if unknown:
            raise MXNetError("op %s: unknown input name(s) %s; inputs are %s"
                             % (op_name, sorted(unknown),
                                list(in_names) + list(aux_names)))
        inputs = []
        for nm in list(in_names) + list(aux_names):
            if nm in by_name:
                inputs.append(head(by_name[nm]))
            else:
                inputs.append(Variable("%s_%s" % (node_name, nm))._outputs[0])
    node = _Node(op, node_name, node_attrs, inputs)
    n_out = node.num_outputs()
    return Symbol([(node, i) for i in range(n_out)]) if n_out > 1 \
        else Symbol([(node, 0)])


def _make_symbol_function(opdef, func_name):
    def creator(*args, **kwargs):
        name = kwargs.pop("name", None)
        attr = kwargs.pop("attr", None)
        sym_kwargs = {}
        attrs = {}
        for k, v in kwargs.items():
            if isinstance(v, Symbol):
                sym_kwargs[k] = v
            else:
                attrs[k] = v
        if opdef.variable_inputs:
            inputs = [a for a in args if isinstance(a, Symbol)]
            if not inputs and sym_kwargs:
                inputs = list(sym_kwargs.values())
            attrs.setdefault("num_args", len(inputs))
            named = None
        else:
            inputs = []
            for a in args:
                if not isinstance(a, Symbol):
                    raise TypeError(
                        "positional args to sym.%s must be Symbols" % func_name)
                inputs.append(a)
            named = sym_kwargs
        extra = attribute.current().get(attr)
        return _create(opdef.name, inputs, attrs, name=name, extra_attr=extra,
                       named_inputs=named)

    creator.__name__ = func_name
    creator.__doc__ = opdef.doc
    return creator


def _init_symbol_module():
    module = globals()
    for reg_name, opdef in list(OP_REGISTRY.items()):
        if reg_name not in module:
            module[reg_name] = _make_symbol_function(opdef, reg_name)


# ---------------------------------------------------------------------------
# JSON load
# ---------------------------------------------------------------------------

def load_json(json_str):
    data = json.loads(json_str)
    jnodes = data["nodes"]
    nodes = []
    for jn in jnodes:
        attrs = jn.get("attrs", jn.get("attr", jn.get("param", {}))) or {}
        if jn["op"] == "null":
            nodes.append(_Node(None, jn["name"], attrs))
        else:
            op = get_op(jn["op"])
            nodes.append(_Node(op, jn["name"], attrs))
    for jn, node in zip(jnodes, nodes):
        node.inputs = [(nodes[i[0]], i[1]) for i in jn["inputs"]]
    heads = data.get("heads")
    if not heads:
        heads = [[len(nodes) - 1, 0, 0]]
    return Symbol([(nodes[h[0]], h[1]) for h in heads])


def load(fname):
    with open(fname) as f:
        return load_json(f.read())


def pow(base, exp):  # noqa: A001 - parity with mx.sym.pow
    return base ** exp


def maximum(left, right):
    if isinstance(left, Symbol) and isinstance(right, Symbol):
        return _create("_maximum", [left, right], {})
    if isinstance(left, Symbol):
        return _create("_maximum_scalar", [left], {"scalar": float(right)})
    return _create("_maximum_scalar", [right], {"scalar": float(left)})


def minimum(left, right):
    if isinstance(left, Symbol) and isinstance(right, Symbol):
        return _create("_minimum", [left, right], {})
    if isinstance(left, Symbol):
        return _create("_minimum_scalar", [left], {"scalar": float(right)})
    return _create("_minimum_scalar", [right], {"scalar": float(left)})
