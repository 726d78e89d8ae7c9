"""What a benchmark cell is: ``BENCHMARK.json``, the configuration,
traffic and metric files it names, the network's shapes and work, and
the peaks table.

Everything here is the yardstick and reads nothing of the program under
test.  A configuration file describes a convolutional network as a list
of nodes (``conv``, ``pool``, ``concat``, ``upsample``) plus an optional
global-mean-pool linear ``head``; shapes are propagated from the input,
so only each conv's kernel size, padding and output width are written
down.  The work counts follow from those shapes alone:

* FLOPs of a conv: ``2 * H_out * W_out * C_out * C_in / groups * K^2``
  per image (the algorithm's, whatever implements it);
* bytes of a step: what any implementation of the whole forward must
  move at least once: the network input, every conv weight and bias,
  and the network output.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    end_to_end: list        # BENCHMARK.json metric entries for this cell
    per_layer: list
    root: str


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT) -> Cell:
    """Resolve workload ``name``: its configuration file (the ``file``
    of its ``configs`` entry), its traffic file
    (``bench/traffic/<traffic>.json``) and the metrics that apply to
    it.  Raises ``KeyError`` for an unknown name."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = read_json(os.path.join(BENCH_DIR, "traffic",
                                     f"{w['traffic']}.json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                root=root)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """Import ``bench/<kind>/<name>.py`` by path (metric readers,
    systems under test, references: one file each, found by name)."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peak_for(device_kind: str, dtype: str, root: str = ROOT) -> dict:
    """``{"flops": ..., "hbm_bytes_per_s": ...}`` of one chip from
    ``bench/peaks.json``.  A device that is not in the table is an
    error, never a default."""
    table = read_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json; have {sorted(table['devices'])}")
    dev = table["devices"][device_kind]
    return {"flops": float(dev["flops"][dtype]),
            "hbm_bytes_per_s": float(dev["hbm_bytes_per_s"])}


# ---------------------------------------------------------------------------
# Network shapes and work
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Node:
    """One node with its propagated shapes (square spatial dims)."""

    name: str
    op: str                     # conv | pool | concat | upsample
    inputs: tuple[str, ...]     # () for the node reading the network input
    size_in: int
    cin: int
    size: int                   # output spatial size
    channels: int               # output channels
    k: int = 1
    pad: int = 0
    groups: int = 1
    window: int = 1
    stride: int = 1
    scale: int = 1

    @property
    def flops(self) -> int:
        if self.op != "conv":
            return 0
        return (2 * self.size * self.size * self.channels
                * (self.cin // self.groups) * self.k * self.k)

    @property
    def n_params(self) -> int:
        if self.op != "conv":
            return 0
        return self.k * self.k * (self.cin // self.groups) * self.channels \
            + self.channels


class NetSpec:
    """A configuration's network with shapes propagated from its input.

    ``channel_divisor`` and ``image`` shrink it for a CPU rehearsal
    (widths divided, image smaller); a benchmark run uses neither."""

    def __init__(self, config: dict, *, channel_divisor: int = 1,
                 image: int | None = None) -> None:
        self.name = config["name"]
        self.dtype = config["dtype"]
        self.image = int(image or config["input"]["size"])
        self.in_channels = int(config["input"]["channels"])
        self.reference = config["reference"]
        head = config.get("head")
        self.classes = int(head["classes"]) if head else None
        nodes: list[Node] = []
        shape: dict[str, tuple[int, int]] = {}
        prev = None
        for raw in config["nodes"]:
            op = raw["op"]
            ins = tuple(raw["in"]) if "in" in raw else \
                ((prev,) if prev else ())
            if ins:
                size_in, cin = shape[ins[0]]
            else:
                size_in, cin = self.image, self.in_channels
            kw = {}
            if op == "conv":
                k, pad = int(raw["k"]), int(raw.get("pad", 0))
                groups = int(raw.get("groups", 1))
                s = int(raw.get("stride", 1))
                cout = max(1, int(raw["cout"]) // channel_divisor)
                size = (size_in + 2 * pad - k) // s + 1
                kw = dict(k=k, pad=pad, groups=groups, stride=s)
                out = (size, cout)
            elif op == "pool":
                w, s = int(raw["window"]), int(raw["stride"])
                out = ((size_in - w) // s + 1, cin)
                kw = dict(window=w, stride=s)
            elif op == "concat":
                sizes = {shape[i][0] for i in ins}
                if len(sizes) != 1:
                    raise ValueError(f"{raw['name']}: concat of sizes "
                                     f"{sorted(sizes)}")
                out = (size_in, sum(shape[i][1] for i in ins))
            elif op == "upsample":
                sc = int(raw["scale"])
                out = (size_in * sc, cin)
                kw = dict(scale=sc)
            else:
                raise ValueError(f"{raw['name']}: unknown op {op!r}")
            node = Node(name=raw["name"], op=op, inputs=ins,
                        size_in=size_in, cin=cin, size=out[0],
                        channels=out[1], **kw)
            if node.name in shape:
                raise ValueError(f"duplicate node name {node.name!r}")
            shape[node.name] = out
            nodes.append(node)
            prev = node.name
        self.nodes = nodes
        self.by_name = {n.name: n for n in nodes}
        last = nodes[-1]
        self.out_shape = (self.classes,) if self.classes else \
            (last.size, last.size, last.channels)

    @property
    def convs(self) -> list[Node]:
        return [n for n in self.nodes if n.op == "conv"]

    @property
    def input_shape(self) -> tuple[int, int, int]:
        return (self.image, self.image, self.in_channels)

    @property
    def conv_flops_per_image(self) -> int:
        return sum(n.flops for n in self.convs)

    @property
    def n_params(self) -> int:
        p = sum(n.n_params for n in self.convs)
        if self.classes:
            p += self.nodes[-1].channels * self.classes + self.classes
        return p

    def step_bytes(self, batch: int) -> int:
        """Least bytes any implementation of one forward of ``batch``
        images moves: input, conv weights and biases, output."""
        b = DTYPE_BYTES[self.dtype]
        inp = batch * self.image * self.image * self.in_channels
        out = batch
        for d in self.out_shape:
            out *= d
        weights = sum(n.n_params for n in self.convs)
        return b * (inp + weights + out)

    def conv_step_bound_s(self, batch: int, peak: dict) -> float:
        """Roofline bound of one step's conv work: the larger of its
        FLOPs over peak FLOP/s and its bytes over peak bytes/s."""
        return max(batch * self.conv_flops_per_image / peak["flops"],
                   self.step_bytes(batch) / peak["hbm_bytes_per_s"])
