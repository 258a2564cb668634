"""Operations and least bytes of one training step, counted from shapes.

The walk reads a configuration's ``architecture`` list, so a new
configuration brings its own list and no code. Conventions:

- a multiply-accumulate is 2 FLOPs; a training step costs 3x the forward
  pass (forward, and two products of the same size per layer backward);
  nothing recomputed counts; elementwise work (relu, pool, V-trace, the
  optimizer) is left out, so the count is the *model's* FLOPs;
- least bytes are what the algorithm must move, not what XLA moves: the
  input once, the parameters once, and each layer's output written once
  forward and read once backward, at the width the configuration computes
  in (``bytes`` on a layer overrides it).
"""

from __future__ import annotations

import math

TRAIN_FLOPS_MULTIPLIER = 3


def conv2d_flops(h_out, w_out, kh, kw, c_in, c_out) -> int:
    return 2 * h_out * w_out * kh * kw * c_in * c_out


def dense_flops(d_in, d_out) -> int:
    return 2 * d_in * d_out


def lstm_flops(d_in, hidden) -> int:
    """One LSTM step on one sample: four gates, two products each."""
    return 2 * 4 * hidden * (d_in + hidden)


def same_out(size: int, stride: int) -> int:
    return math.ceil(size / stride)


def walk(architecture, act_bytes: int = 2):
    """Per-frame records ``(name, forward_flops, params, out_elems,
    out_bytes)`` of each layer, in order."""
    h = w = c = flat = None
    side = 0
    records = []
    input_bytes = 0
    for layer in architecture:
        op = layer["op"]
        width = layer.get("bytes", act_bytes)
        for _ in range(layer.get("repeat", 1)):
            if op == "input":
                h, w, c = layer["shape"]
                input_bytes += h * w * c * width
            elif op == "embed":
                # A gather: no products; its rows are its parameters.
                records.append((f"embed {layer['rows']}x{layer['out']}", 0,
                                layer["rows"] * layer["out"],
                                h * w * layer["out"],
                                h * w * layer["out"] * width))
                c = layer["out"]
            elif op == "conv":
                k, s = layer["kernel"], layer["stride"]
                ho, wo = same_out(h, s), same_out(w, s)
                records.append((
                    f"conv{k}x{k}/{s} {c}->{layer['out']} @{ho}x{wo}",
                    conv2d_flops(ho, wo, k, k, c, layer["out"]),
                    k * k * c * layer["out"] + layer["out"],
                    ho * wo * layer["out"], ho * wo * layer["out"] * width,
                ))
                h, w, c = ho, wo, layer["out"]
            elif op == "pool":
                h, w = same_out(h, layer["stride"]), same_out(w, layer["stride"])
                records.append((f"pool/{layer['stride']} @{h}x{w}", 0, 0,
                                h * w * c, h * w * c * width))
            elif op == "flatten":
                flat = h * w * c
            elif op == "side_dense":
                input_bytes += layer["in"] * layer.get("input_bytes", width)
                records.append((
                    f"side dense {layer['in']}->{layer['out']}",
                    dense_flops(layer["in"], layer["out"]),
                    layer["in"] * layer["out"] + layer["out"],
                    layer["out"], layer["out"] * width,
                ))
                side += layer["out"]
            elif op == "dense":
                d_in = flat + side
                records.append((
                    f"dense {d_in}->{layer['out']}",
                    dense_flops(d_in, layer["out"]),
                    d_in * layer["out"] + layer["out"],
                    layer["out"], layer["out"] * width,
                ))
                flat, side = layer["out"], 0
            elif op == "lstm":
                records.append((
                    f"lstm {flat}+{layer['out']}",
                    lstm_flops(flat, layer["out"]),
                    4 * layer["out"] * (flat + layer["out"]) + 4 * layer["out"],
                    # h and c of every step are kept for the backward pass
                    2 * layer["out"], 2 * layer["out"] * width,
                ))
                flat = layer["out"]
            elif op == "heads":
                for out in layer["outs"]:
                    records.append((
                        f"head {flat}->{out}", dense_flops(flat, out),
                        flat * out + out, out, out * width,
                    ))
            else:
                raise ValueError(f"unknown architecture op {op!r}")
    return records, input_bytes


def train_step(config: dict, frames: int, param_bytes: int = 4) -> dict:
    """FLOPs and least bytes of one training step over ``frames`` frames
    (``(T + 1) * B``: the bootstrap frame is real compute)."""
    act_bytes = {"bfloat16": 2, "float32": 4}[config["precision"]]
    records, input_bytes = walk(config["architecture"], act_bytes)
    forward = sum(r[1] for r in records)
    params = sum(r[2] for r in records)
    activations = sum(r[4] for r in records)
    return {
        "flops": TRAIN_FLOPS_MULTIPLIER * frames * forward,
        "forward_flops_per_frame": forward,
        "params": params,
        "least_bytes": frames * (input_bytes + 2 * activations)
        + params * param_bytes,
        "layers": records,
    }


def roofline(config: dict, frames: int, peaks: dict) -> dict:
    """The least time one step could take on a chip with these peaks, and
    which of the two bounds it."""
    c = train_step(config, frames)
    t_flops = c["flops"] / peaks["flops_per_s"]
    t_bytes = c["least_bytes"] / peaks["hbm_bytes_per_s"]
    return {
        **c, "flops_seconds": t_flops, "bytes_seconds": t_bytes,
        "least_seconds": max(t_flops, t_bytes),
        "bound_by": "flops" if t_flops >= t_bytes else "bytes",
    }
