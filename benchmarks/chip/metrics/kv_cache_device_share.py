"""KV cache: share of the device's busy time over the traced window spent
in operations whose result is a whole layer's cache or the stacked cache
of all layers, [..., batch, max_len, kv_heads, head_dim]: the layout
copies, and the slicing of each layer's cache out of the stack and its
writing back.  Attention's arithmetic over the cache is not counted."""
import re

# a label is "<instruction> <dtype>[d0,d1,...]" (profile_trace.op_name)
SHAPE = re.compile(r" \w+\[([\d,]*)\]$")


def read(run):
    t = run.trace
    if t is None:
        return None
    m, s = run.model, run.serve
    cache = (s["batch"], s["max_len"], m["n_kv_heads"], m["head_dim"])
    seconds = 0.0
    for label, sec in t["label_s"].items():
        shape = SHAPE.search(label)
        dims = tuple(int(d) for d in shape.group(1).split(",")
                     if d) if shape else ()
        if dims[-4:] == cache:
            seconds += sec
    if seconds <= 0.0:
        return None
    return 100.0 * seconds / t["busy_s"]
