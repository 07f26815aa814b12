#!/usr/bin/env python3
"""Rehearsal 3: compile each cell's programs at the REAL size for a
described ``v5e:2x2``, here, without the chip.

    JAX_PLATFORMS=cpu python benchmark/selftest/aot_compile.py \
        [--root DIR] [--log2n N] [cell ...]

The cells are those of the root's ``BENCHMARK.json`` (default: the
repo's; ``--root benchmark/selftest/next`` compiles the rehearsal
deployment), each compiled as its workload's ``driver`` builds it.
``--log2n N`` compiles the same configuration with 2^N-sample segments
instead of its own size: how the cut of ``baseband_input_count`` is held
against the sizes between it and the source's (PERF.md section 4).

What the chip's compiler refuses here costs no chip time.  Nothing runs:
a compile that passes is not a chip run.  The served cell's plan is built
as ``Pipeline`` builds it (``registry.build_processor``) and every program
of the plan is compiled for one described chip; the grid cell's step is
``DistSegmentProcessor``'s jitted ``shard_map`` over a ("dm", "seq") mesh
of the four described chips.  That class places its constants on its mesh
as it is built, which a described device cannot hold, so THIS SCRIPT (not
an option of the program) hands it shapes instead of arrays.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def report(name: str, compiled, t0: float) -> None:
    m = compiled.memory_analysis()
    text = compiled.as_text()
    coll = {k: text.count(f" {k}(") + text.count(f" {k}-start(")
            for k in ("all-reduce", "collective-permute", "all-to-all",
                      "all-gather")}
    print(f"[aot] {name}: compiled in {time.perf_counter() - t0:.1f} s; "
          f"temp {m.temp_size_in_bytes / 1e9:.2f} GB + arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} GB + output "
          f"{m.output_size_in_bytes / 1e9:.2f} GB per device; "
          f"collectives {coll}", flush=True)


LOG2N = None       # --log2n: another segment size than the file's


def options_argv(config_file: str) -> list:
    with open(os.path.join(ROOT, config_file)) as f:
        options = json.load(f)["options"]
    if LOG2N is not None:
        options["baseband_input_count"] = f"2 ** {LOG2N}"
    return [f"--{k}={v}" for k, v in options.items()]


def served(topo, config_file: str) -> None:
    import jax
    from jax.sharding import SingleDeviceSharding

    from srtb_tpu.config import Config
    from srtb_tpu.pipeline import registry

    chip = SingleDeviceSharding(topo.devices[0])
    cfg = Config.from_args(options_argv(config_file))
    proc = registry.build_processor(cfg, donate_input=True)
    print(f"[aot] served plan {getattr(proc, 'plan_name', '?')}")
    for name, fn, avals, _donated in proc.lowerables():
        t0 = time.perf_counter()
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), avals)
        report(name, fn.lower(*shapes).compile(), t0)


def dmgrid(topo, config_file: str) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from srtb_tpu.config import Config
    from srtb_tpu.parallel import segment_dist

    cfg = Config.from_args(options_argv(config_file))
    devices = np.array(topo.devices[:4]).reshape(4, 1)
    mesh = Mesh(devices, ("dm", "seq"))
    segment_dist._put_sharded = lambda host, sharding: \
        jax.ShapeDtypeStruct(np.shape(host), np.asarray(host).dtype,
                             sharding=sharding)
    proc = segment_dist.DistSegmentProcessor(cfg, mesh, list(cfg.dm_list))
    raw = jax.ShapeDtypeStruct((cfg.segment_bytes(1),), np.uint8,
                               sharding=NamedSharding(mesh, P("seq")))
    args = [raw, proc.chirp_bank, proc.rfi_mask]
    if proc.window is not None:
        args.append(proc.window)
    t0 = time.perf_counter()
    report("dmgrid step, mesh 4x1", proc._step.lower(*args).compile(), t0)


COMPILERS = {"served": served, "dmgrid": dmgrid}


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.join(ROOT, "benchmark"))
    ap.add_argument("--log2n", type=int, default=None)
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args(argv[1:])

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from benchmark import spec as spec_mod

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    global LOG2N
    LOG2N = args.log2n
    root = os.path.abspath(args.root)
    with open(spec_mod.find_benchmark_json(root)) as f:
        bench = json.load(f)
    configs = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        name = cell["name"]
        if args.cells and name not in args.cells:
            continue
        with open(os.path.join(root, "workloads", f"{name}.json")) as f:
            driver = json.load(f)["driver"]
        print(f"[aot] {name}" + (f" at 2^{LOG2N}" if LOG2N else ""),
              flush=True)
        try:
            COMPILERS[driver](topo, configs[cell["config"]])
        except Exception as e:  # the compiler's refusal is the finding
            text = str(e)
            print(f"[aot] {name}: REFUSED: {type(e).__name__}: "
                  f"{text[:1500]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
