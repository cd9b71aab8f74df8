"""Device time by the program's named scopes: the join of an ``XLA Ops``
event of a profiler trace with the instruction of the compiled step's HLO
text that it ran, and through that instruction's ``op_name`` with the
scopes the program put there.

The program wraps every operator of a symbol in
``jax.named_scope('<Operator>/<node>')`` and the parts of its fused step in
``forward_backward``, ``optimizer`` and ``metric``; an operator may open
scopes of its own inside (``SparseExperts``: ``router``, ``dispatch``,
``experts``, ``combine``).  JAX writes the stack of scopes into each HLO
instruction's ``metadata={op_name="..."}``, wrapped in the names of its own
transformations: ``jit(step_m)/forward_backward/jvp(SparseExperts/l1_moe)/
router/top_k`` forward, ``.../transpose(jvp(forward_backward))/jvp()/
checkpoint/SparseExperts/l1_moe/router/mul`` backward, ``.../checkpoint/
rematted_computation/...`` where a mirror stage is computed again,
``.../SparseExperts/l1_moe/cond/branch_0_fun/transpose(jvp(experts))/...``
inside a branch of a ``cond`` that takes its own backward pass.  An op
event's name is the whole instruction, ``%name = type opcode(operands),
...``, without its metadata; its ``%name`` is the key.

Which scope an instruction belongs to:

1. its own ``op_name``, if that holds an ``<Operator>/<node>`` pair of the
   symbol;
2. a fusion (or another instruction that ``calls=`` a computation) without
   one goes to its root's scope;
3. what the compiler made itself and named itself (the sort of an
   ``argsort``, the grouped product ``ragged-dot``, copies, the kernels'
   metadata) has lost the whole stack, the step's part too: it takes the
   part, operator and node of the nearest operand that has them, and its
   inner scope from ``KERNELS`` if its name is there.  An instruction that
   still names its part (the optimizer's update of a parameter, the cast
   of a parameter to bf16) belongs to no operator and inherits nothing.

Which pass it belongs to (``StepScopes.recomputed``): an instruction
whose ``op_name`` (its own, or by rule 2 its root's) has
``rematted_computation`` in it is part of a forward pass computed again for
the backward pass.  What inherits its scope (rule 3) has no such word and
cannot be told from its neighbours: fusions mix the passes, and a
gradient's product takes what was recomputed beside a cotangent.  Those
are counted instead (``kernel_instructions``): twelve grouped products a
layer where the model has nine say that three are a forward pass again.
"""
import collections
import re

from . import trace_reduce

STEP_PARTS = ('forward_backward', 'optimizer', 'metric')
# instructions XLA names itself, by the start of their name: the inner scope
# of the operator they implement
KERNELS = {'ragged-dot': 'experts'}
# the small kernels that lay out a grouped product's groups: the operator's
# too, and not counted among its products
KERNEL_HELPERS = ('ragged-dot-metadata',)
INHERIT_DEPTH = 8

_DEFINITION = re.compile(r'^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$')
_HEADER = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r'(?:calls|to_apply|body)=%?([\w.\-]+)')
_OPERAND = re.compile(r'%([\w.\-]+)')
_SPLIT = re.compile(r'[/()]')
# JAX's own words between an operator's node and the scope it opened: a
# branch of a ``cond``, and the transformations of a backward pass taken
# inside one
_WRAPPERS = re.compile(r'(cond|branch_\d+_fun|jvp|transpose)$')

Scope = collections.namedtuple('Scope', 'part operator node inner')
NO_SCOPE = Scope(None, None, None, None)


def event_instruction(text):
    """The instruction's name in an op event's text."""
    return text.partition(' = ')[0].strip().lstrip('%')


def _operands(rhs):
    """Names of the operands of an instruction's right-hand side."""
    match = trace_reduce._OPCODE.match(rhs)
    if not match:
        return []
    depth, start = 0, match.end() - 1
    for index in range(start, len(rhs)):
        if rhs[index] == '(':
            depth += 1
        elif rhs[index] == ')':
            depth -= 1
            if depth == 0:
                return _OPERAND.findall(rhs[start:index])
    return []


class StepScopes(object):
    """The scopes of one compiled program's instructions.

    ``hlo_text`` is ``Compiled.as_text()``; ``pairs`` the symbol's
    ``(operator, node name)`` pairs."""

    def __init__(self, hlo_text, pairs):
        self.pairs = set(tuple(p) for p in pairs)
        self.op_name, self.calls, self.operands = {}, {}, {}
        self.root = {}              # computation -> its root instruction
        computation = None
        for line in hlo_text.splitlines():
            header = _HEADER.match(line)
            if header:
                computation = header.group(1)
                continue
            definition = _DEFINITION.match(line)
            if not definition:
                continue
            is_root, name, rhs = definition.groups()
            if is_root and computation is not None:
                self.root[computation] = name
            found = _OP_NAME.search(rhs)
            if found:
                self.op_name[name] = found.group(1)
            called = _CALLS.search(rhs)
            if called:
                self.calls[name] = called.group(1)
            self.operands[name] = _operands(rhs)
        self._memo = {}

    def parse(self, op_name):
        """The scope an ``op_name`` states, or ``NO_SCOPE``."""
        words = [w for w in _SPLIT.split(op_name or '') if w]
        part = next((w for w in words if w in STEP_PARTS), None)
        for index in range(len(words) - 1):
            if (words[index], words[index + 1]) in self.pairs:
                inner = next((w for w in words[index + 2:]
                              if not _WRAPPERS.match(w)), None)
                return Scope(part, words[index], words[index + 1], inner)
        return Scope(part, None, None, None)

    def _own(self, name, depth=0):
        """Rules 1 and 2."""
        scope = self.parse(self.op_name.get(name))
        if scope.operator is None and name in self.calls and depth < 4:
            root = self.root.get(self.calls[name])
            if root is not None:
                below = self._own(root, depth + 1)
                if below.operator is not None or scope.part is None:
                    return below
        return scope

    def recomputed(self, name, depth=0):
        """Whether the instruction called ``name`` says of itself (rules 1
        and 2) that it is part of a forward pass computed again."""
        op_name = self.op_name.get(name)
        if op_name is None and name in self.calls and depth < 4:
            root = self.root.get(self.calls[name])
            return root is not None and self.recomputed(root, depth + 1)
        return 'rematted_computation' in (op_name or '')

    def of(self, name):
        """The scope of the instruction called ``name``."""
        if name in self._memo:
            return self._memo[name]
        scope = self._own(name)
        if scope.operator is None and scope.part is None:
            # rule 3: the nearest operand that has a scope, breadth first
            seen, frontier = {name}, [name]
            for _ in range(INHERIT_DEPTH):
                frontier = [o for n in frontier
                            for o in self.operands.get(n, ())
                            if o not in seen and not seen.add(o)]
                found = [s for s in (self._own(o) for o in frontier)
                         if s.operator is not None]
                if found or not frontier:
                    break
            else:
                found = []
            if found:
                inner = next((v for k, v in KERNELS.items()
                              if name.startswith(k)), None)
                scope = Scope(found[0].part or scope.part,
                              found[0].operator, found[0].node, inner)
        self._memo[name] = scope
        return scope


def reduce_scopes(profile, hlo_text, pairs, span_name='bench.slice',
                  chips=None):
    """Seconds of device time over the traced slice, mean over the chips:
    ``busy_s`` (all op events), ``by_part``, ``by_operator``, ``by_node``
    (``<Operator>/<node>``), ``by_inner`` (``<Operator>/<inner scope>``),
    ``recomputed_by_operator`` and ``recomputed_by_inner`` (the part of the
    two before that says of itself that it is a forward pass computed
    again), ``kernel_instructions`` (``<Operator>/<inner scope>`` -> how
    many distinct instructions of ``KERNELS`` ran under it on the first
    chip), ``joined_s`` (events whose instruction the text has) and
    ``scoped_s`` (events that got an operator).  None without a device
    plane or a text."""
    if not hlo_text:
        return None
    window = trace_reduce.window_of(profile, span_name)
    planes = trace_reduce.device_planes(profile)
    if chips is not None:
        planes = planes[:chips]
    if not planes or window is None:
        return None
    scopes = StepScopes(hlo_text, pairs)
    totals = collections.defaultdict(lambda: collections.defaultdict(float))
    count = float(len(planes))
    kernels = collections.defaultdict(set)
    for plane in planes:
        for start, end, text in trace_reduce.DeviceOps(plane, *window).sync:
            if ' conditional(' in text:
                # its event lasts as long as the branch it ran, whose
                # instructions have events of their own
                continue
            seconds = (end - start) / 1e9 / count
            name = event_instruction(text)
            totals['all']['busy_s'] += seconds
            if name not in scopes.operands:
                continue
            totals['all']['joined_s'] += seconds
            scope = scopes.of(name)
            if scope.part:
                totals['by_part'][scope.part] += seconds
            if scope.operator is None:
                continue
            totals['all']['scoped_s'] += seconds
            totals['by_operator'][scope.operator] += seconds
            totals['by_node']['%s/%s' % (scope.operator, scope.node)] += \
                seconds
            again = scopes.recomputed(name)
            if again:
                totals['recomputed_by_operator'][scope.operator] += seconds
            if scope.inner:
                inner = '%s/%s' % (scope.operator, scope.inner)
                totals['by_inner'][inner] += seconds
                if again:
                    totals['recomputed_by_inner'][inner] += seconds
                if plane is planes[0] and name.startswith(tuple(KERNELS)) \
                        and not name.startswith(KERNEL_HELPERS):
                    kernels[inner].add(name)
    out = {k: dict(v) for k, v in totals.items() if k != 'all'}
    for key in ('by_part', 'by_operator', 'by_node', 'by_inner',
                'recomputed_by_operator', 'recomputed_by_inner'):
        out.setdefault(key, {})
    for key in ('busy_s', 'joined_s', 'scoped_s'):
        out[key] = totals['all'].get(key, 0.0)
    out['kernel_instructions'] = {k: len(v) for k, v in kernels.items()}
    return out
