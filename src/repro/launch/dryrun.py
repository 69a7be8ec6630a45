import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks the device count on first
# init).  This module is the ONLY place the 512-device override is set.

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs.registry import get_arch, list_archs  # noqa: E402
from ..models import common  # noqa: E402
from ..roofline import analysis  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _mem_stats(compiled):
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
        out["per_device_total_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    except Exception as e:  # pragma: no cover
        out["error"] = str(e)
    return out


def _sharding_tree(spec_tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def lower_cell(arch_id: str, shape: str, multi_pod: bool):
    """Lower + compile one (arch x shape x mesh) cell; return stats."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    arch = get_arch(arch_id)
    t0 = time.time()

    if arch.family == "mining":
        from ..mining.distributed import make_mining_step

        m = arch.shapes[shape].meta
        db_axes = common.dp_axes(mesh)
        step = make_mining_step(mesh, k=m["k"], db_axes=db_axes,
                                tok_axis="model")
        b = arch.batch_abstract(shape)
        args = (b["tokens"], b["gid"], b["phi"], b["psi"], b["valid"],
                b["existing"], jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
        with jax.set_mesh(mesh):
            lowered = step.lower(*args)
    else:
        step, args = arch.make_step(shape, mesh)
        specs = arch.arg_specs(shape, mesh, args)
        shardings = _sharding_tree(specs, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=shardings).lower(*args)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    hlo = compiled.as_text()
    n_chips = int(np.prod(list(mesh.shape.values())))
    # the virtual CPU devices stand in for a v5e pod's chips
    roof = analysis.from_compiled(
        compiled, n_chips, arch.model_flops(shape),
        device_kind="TPU v5 lite", hlo_text=hlo,
    )
    coll = analysis.parse_collectives(hlo)
    return {
        "arch": arch_id,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "ok": True,
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "memory": _mem_stats(compiled),
        "collectives": coll,
        "roofline": roof.to_dict(),
    }


def run_cell_to_file(arch_id, shape, multi_pod, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch_id}__{shape}__{'multi' if multi_pod else 'single'}"
    path = os.path.join(out_dir, tag + ".json")
    try:
        res = lower_cell(arch_id, shape, multi_pod)
        print(f"[dryrun] OK   {tag}  compile={res['t_compile_s']}s "
              f"bottleneck={res['roofline']['bottleneck']}")
    except Exception as e:
        res = {
            "arch": arch_id, "shape": shape,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "ok": False, "error": str(e),
            "traceback": traceback.format_exc(),
        }
        print(f"[dryrun] FAIL {tag}: {e}")
    with open(path, "w") as f:
        json.dump(res, f, indent=2)
    return res


def all_cells(include_mining=True):
    cells = []
    for arch_id in list_archs(include_extra=include_mining):
        arch = get_arch(arch_id)
        for shape in arch.shapes:
            cells.append((arch_id, shape))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.arch and args.shape:
        cells = [(args.arch, args.shape)]
    elif args.arch:
        cells = [(args.arch, s) for s in get_arch(args.arch).shapes]
    else:
        cells = all_cells()

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch_id, shape in cells:
        for multi in meshes:
            tag = (f"{arch_id}__{shape}__"
                   f"{'multi' if multi else 'single'}")
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                try:
                    ok = json.load(open(path)).get("ok")
                except Exception:
                    ok = False
                if ok:
                    print(f"[dryrun] SKIP {tag}")
                    continue
            run_cell_to_file(arch_id, shape, multi, args.out)


if __name__ == "__main__":
    main()
