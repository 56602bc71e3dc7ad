"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: for each
assigned architecture and input shape, the full-size model step is
``jit(...).lower(...).compile()``-ed against the 16×16 single-pod mesh
and the 2×16×16 multi-pod mesh (512 placeholder host devices).  Sharding
mismatches, compile-time OOMs and unsupported collectives surface here
as hard failures.

Outputs per cell: per-device memory analysis (proves it fits a 16 GB
v5e chip), per-device cost analysis (FLOPs/bytes), and the collective-op
census parsed from the compiled HLO — consumed by ``repro.roofline``.

Usage::

    python -m repro.launch.dryrun --all [--out experiments/dryrun.json]
    python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
"""
# The device-count override MUST precede any other import that could
# initialize jax (jax locks the device count on first backend init).
import os  # noqa: E402
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                              # noqa: E402
from repro.configs.shapes import SHAPES, applicable    # noqa: E402
from repro.distribution.sharding import (              # noqa: E402
    param_sharding_tree, sharding_ctx)
from repro.launch.mesh import make_ctx, make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs, param_shapes      # noqa: E402
from repro.models.transformer import build_model       # noqa: E402
from repro.training.optimizer import OptCfg, OptState  # noqa: E402
from repro.training.train import (TrainState, build_train_step,  # noqa: E402
                                  init_train_state)

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|"
                       r"pred)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8,
                "s16": 2, "u16": 2}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = _DTYPE_BYTES[dt]
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def collective_census(hlo: str, pod_stride: int | None = None) -> dict:
    """Sum result bytes of every collective op kind in (post-SPMD) HLO.

    HLO line form: ``%name = TYPE[dims]{layout} all-reduce(...)``; for
    variadic collectives the type is a tuple — all element shapes are
    summed.  When ``pod_stride`` is given, collectives whose replica
    group spans device ids across a pod boundary are tallied separately
    (``cross_pod`` — these ride the slow inter-pod links).

    Note: while-loop (scan) bodies appear once in the text; the roofline
    pass uses unrolled lowerings where this census is exact.
    """
    out: dict[str, float] = {}
    cross_pod = 0.0
    for line in hlo.splitlines():
        for op in _COLL_OPS:
            tok = f" {op}("
            if tok not in line and f" {op}-start(" not in line:
                continue
            head = line.split(tok)[0] if tok in line else \
                line.split(f" {op}-start(")[0]
            if "=" not in head:
                continue
            type_str = head.split("=", 1)[1]
            nbytes = _shape_bytes(type_str)
            out[op] = out.get(op, 0) + nbytes
            if pod_stride:
                g = _GROUPS_RE.search(line)
                if g:
                    ids = [int(x) for x in g.group(1).split(",")]
                    if len({i // pod_stride for i in ids}) > 1:
                        cross_pod += nbytes
            break
    out["total"] = sum(out.values())
    if pod_stride:
        out["cross_pod"] = cross_pod
    return out


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    status: str               # ok | skip | fail
    reason: str = ""
    seconds: float = 0.0
    flops: float = 0.0                # per-device, from cost_analysis
    bytes_accessed: float = 0.0       # per-device
    peak_memory_bytes: float = 0.0    # per-device
    arg_bytes: float = 0.0
    temp_bytes: float = 0.0
    collectives: dict = dataclasses.field(default_factory=dict)

    def row(self):
        return dataclasses.asdict(self)


def _step_builder(model, shape_cfg):
    """Returns (fn, args_dict, in_specs_dict) for this shape's step."""
    args, specs = input_specs(model, shape_cfg)
    if shape_cfg.kind == "train":
        step = build_train_step(model, OptCfg())
        state_shapes = jax.eval_shape(
            lambda: init_train_state(model, jax.random.key(0)))
        ps = model.param_specs()
        state_specs = TrainState(params=ps,
                                 opt=OptState(m=ps, v=ps, step=P()),
                                 err=None)
        fn = lambda state, tokens, labels: step(state, tokens, labels)  # noqa
        all_args = (state_shapes, args["tokens"], args["labels"])
        all_specs = (state_specs, specs["tokens"], specs["labels"])
        return fn, all_args, all_specs
    p_shapes = param_shapes(model)
    p_specs = model.param_specs()
    if shape_cfg.kind == "prefill":
        fn = model.prefill
        return fn, (p_shapes, args["tokens"], args["cache"]), \
            (p_specs, specs["tokens"], specs["cache"])
    fn = model.decode_step
    return fn, (p_shapes, args["tok"], args["cache"], args["pos"]), \
        (p_specs, specs["tok"], specs["cache"], specs["pos"])


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             layer_mode: str = "scan", n_layers: int | None = None,
             attn_impl: str | None = None, want_hlo: bool = False,
             rule_overrides: dict | None = None,
             cfg_overrides: dict | None = None,
             collect_hlo_census: bool = True) -> CellResult:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cfg = configs.get(arch)
    shape_cfg = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape_cfg)
    res = CellResult(arch=arch, shape=shape_name, mesh=mesh_name,
                     status="skip", reason=reason)
    if not ok:
        return res
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    if attn_impl is not None:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    if cfg_overrides:
        # nested dataclass fields addressed as "moe.capacity_factor"
        plain = {k: v for k, v in cfg_overrides.items() if "." not in k}
        cfg = dataclasses.replace(cfg, **plain)
        for k, v in cfg_overrides.items():
            if "." in k:
                head, leaf = k.split(".", 1)
                sub = dataclasses.replace(getattr(cfg, head), **{leaf: v})
                cfg = dataclasses.replace(cfg, **{head: sub})
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = make_ctx(mesh, cfg, shape_cfg, **(rule_overrides or {}))
    try:
        with sharding_ctx(ctx):
            model = build_model(cfg, layer_mode=layer_mode)
            fn, arg_shapes, arg_specs = _step_builder(model, shape_cfg)
            shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), arg_specs,
                is_leaf=lambda s: isinstance(s, P))
            donate = (0,) if shape_cfg.kind == "train" else \
                ((2,) if shape_cfg.kind == "prefill" else (2,))
            lowered = jax.jit(fn, in_shardings=shardings,
                              donate_argnums=donate).lower(*arg_shapes)
            compiled = lowered.compile()
            ca = compiled.cost_analysis() or {}
            ma = compiled.memory_analysis()
            res.status = "ok"
            res.flops = float(ca.get("flops", 0.0))
            res.bytes_accessed = float(ca.get("bytes accessed", 0.0))
            if ma is not None:
                res.peak_memory_bytes = float(
                    getattr(ma, "peak_memory_in_bytes", 0) or
                    (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes))
                res.arg_bytes = float(ma.argument_size_in_bytes)
                res.temp_bytes = float(ma.temp_size_in_bytes)
            if collect_hlo_census:
                res.collectives = collective_census(
                    compiled.as_text(),
                    pod_stride=256 if multi_pod else None)
            if want_hlo:
                res.collectives["_hlo"] = compiled.as_text()
    except Exception as e:                                 # noqa: BLE001
        res.status = "fail"
        res.reason = f"{type(e).__name__}: {e}"[:500]
    res.seconds = time.time() - t0
    return res


def iter_cells():
    for arch in configs.ARCH_NAMES:
        for shape in SHAPES:
            yield arch, shape


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun.json")
    args = ap.parse_args()

    cells = list(iter_cells()) if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    rows = []
    for arch, shape in cells:
        for mp in meshes:
            r = run_cell(arch, shape, multi_pod=mp)
            coll = r.collectives.get("total", 0) / 1e6
            print(f"{arch:18s} {shape:12s} {r.mesh:8s} {r.status:5s} "
                  f"t={r.seconds:6.1f}s flops/dev={r.flops:.3e} "
                  f"peak_mem/dev={r.peak_memory_bytes/2**30:6.2f}GiB "
                  f"coll={coll:9.1f}MB {r.reason[:60]}",
                  flush=True)
            rows.append(r.row())
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"wrote {args.out}")
    n_fail = sum(1 for r in rows if r["status"] == "fail")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
