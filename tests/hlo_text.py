"""Reading compiled HLO text in tests: which instructions write a whole
buffer of a given shape by a broadcast or a copy."""
import re

_HLO_DT = {"bf16": "bfloat16", "f32": "float32", "s8": "int8",
           "f8e4m3fn": "float8_e4m3fn"}
_HLO_INSTR = re.compile(
    r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\((.*)")


def whole_buffer_fills_and_copies(text, shapes):
    """Instructions of a compiled HLO module that materialize a broadcast
    or a copy of one of ``shapes`` ((dtype, dims) pairs, dims joined by
    commas): ``broadcast`` and ``copy`` ops outside fused computations, and
    fusions whose root, past any bitcasts, is one. A copy that changes only
    the layout (a relayout) counts. Ops inside a fusion that computes
    something else write no buffer of their own."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head and line.rstrip().endswith("{"):
            cur = comps.setdefault(head.group(1), {})
            continue
        m = _HLO_INSTR.match(line)
        if cur is not None and m:
            name, dt, dims, op, rest = m.groups()
            cur[name] = dict(
                op=op, shape=(_HLO_DT.get(dt, dt), dims),
                operands=re.findall(r"%([\w.\-]+)", rest.split(")")[0]),
                root=line.lstrip().startswith("ROOT"),
                calls=re.findall(r"calls=%([\w.\-]+)", rest))

    def made_by(ins):
        if ins["op"] != "fusion":
            return ins["op"]
        body = comps[ins["calls"][0]]
        root = next(v for v in body.values() if v["root"])
        while root["op"] == "bitcast":
            root = body[root["operands"][0]]
        return root["op"]

    fused = {c for ins in comps.values() for v in ins.values()
             if v["op"] == "fusion" for c in v["calls"]}
    return [(name, v["shape"]) for c, ins in comps.items() if c not in fused
            for name, v in ins.items()
            if v["shape"] in shapes and made_by(v) in ("broadcast", "copy")]


def pool_shapes(state):
    """(dtype, dims) of every stacked pool leaf of a serving state's caches,
    target and draft, as ``whole_buffer_fills_and_copies`` takes them."""
    import jax
    return {(str(x.dtype), ",".join(map(str, x.shape)))
            for c in (state.tcache, state.dcache)
            for x in jax.tree.leaves(c["scan"])}
