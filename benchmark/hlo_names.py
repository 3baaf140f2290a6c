"""A name for the operations the compiler made. XLA gives an instruction
it creates itself (a copy into another layout or memory space, a fusion
whose root is a convert it inserted) no `op_name`, so the trace shows no
`tf_op` for it, though it works for an operation that has one. The trace
carries every program's HLO (`/host:metadata` plane, one `Hlo Proto` per
program id); from it such an instruction takes the `op_name` of the
nearest instruction that has one: inside a fusion's computation searching
back from its root (a fusion goes where its root goes, the compiler's
wrapping skipped), else forward through its users, breadth first, in its
own computation. An instruction that JAX named is never renamed: one
whose path holds none of the program's scopes stays unscoped."""

from __future__ import annotations

import collections
import re

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")


def _skip_shape(rest):
    """`rest` after " = ": the text from the opcode on."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return rest[i + 1:].lstrip()
    return rest.partition(" ")[2]


def _operands(call):
    """Names inside the opcode's own parentheses."""
    depth, start = 0, None
    for i, ch in enumerate(call):
        if ch == "(":
            depth += 1
            if depth == 1:
                start = i + 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                inner = call[start:i]
                break
    else:
        return []
    if "%" in inner:  # printed with percent signs (and maybe shapes)
        return re.findall(r"%([^\s,()]+)", inner)
    return [p.strip() for p in inner.split(",") if p.strip()]


class Module:
    """One HLO module's instructions: `op_name[name]`, `operands[name]`,
    `users[name]`, `calls[name]` (a fusion's computation) and each
    computation's `root`."""

    def __init__(self, text):
        self.op_name, self.operands, self.calls = {}, {}, {}
        self.users = collections.defaultdict(list)
        self.root, self.members = {}, collections.defaultdict(list)
        comp = None
        for line in text.splitlines():
            if line.endswith("{") and " = " not in line:
                head = line.strip().removeprefix("ENTRY ").lstrip("%")
                comp = re.split(r"[\s(]", head, maxsplit=1)[0]
                continue
            m = _INSTR.match(line)
            if not m or comp is None:
                continue
            is_root, name, rest = m.groups()
            call = _skip_shape(rest)
            found = _OP_NAME.search(call)
            self.op_name[name] = found.group(1) if found else ""
            self.operands[name] = _operands(call)
            for o in self.operands[name]:
                self.users[o].append(name)
            called = _CALLS.search(call)
            if called and call.startswith("fusion("):
                self.calls[name] = called.group(1)
            self.members[comp].append(name)
            if is_root:
                self.root[comp] = name

    def _nearest(self, start, step):
        seen, queue = {start}, collections.deque([start])
        while queue:
            name = queue.popleft()
            if self.op_name.get(name):
                return self.op_name[name]
            for nxt in step(name):
                if nxt not in seen:
                    seen.add(nxt)
                    queue.append(nxt)
        return ""

    def resolve(self, name):
        """`name`'s own `op_name`, else its nearest named neighbour's."""
        name = name.lstrip("%")
        if self.op_name.get(name) or name not in self.op_name:
            return self.op_name.get(name, "")
        comp = self.calls.get(name)
        if comp in self.root:
            found = self._nearest(
                self.root[comp], lambda n: self.operands.get(n, ()))
            if found:
                return found
        return self._nearest(name, lambda n: self.users.get(n, ()))


def _varint(data, i):
    value = shift = 0
    while True:
        byte = data[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _first_field(data, want):
    """The bytes of a proto's first length-delimited field `want`."""
    i = 0
    while i < len(data):
        key, i = _varint(data, i)
        number, wire = key >> 3, key & 7
        if wire == 2:
            n, i = _varint(data, i)
            if number == want:
                return data[i:i + n]
            i += n
        elif wire == 0:
            _, i = _varint(data, i)
        else:
            i += 8 if wire == 1 else 4
    return None


def module_text(hlo_proto_bytes):
    """HLO text, with metadata, of a serialized `HloProto` (its
    `hlo_module` is field 1), printed by jax's own XLA client."""
    from jax._src.lib import xla_client as xc
    module = xc.XlaComputation(
        _first_field(hlo_proto_bytes, 1)).get_hlo_module()
    opts = xc._xla.HloPrintOptions()
    opts.print_metadata, opts.print_backend_config = True, False
    opts.print_large_constants = opts.print_operand_shape = False
    opts.print_percent = False
    return module.to_string(opts)
