import os

# a host-device study: pin to the CPU so that, on a machine with a chip, the
# child never reaches for the accelerator the parent process holds
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

"""Child process for bench_memory: lowers a reduced llama prefill on an
8-device host mesh under two policies and prints per-device bytes.  Runs in
its own process because the parent's jax is already initialized with one
device."""
import jax

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh, mesh_axes_dict
from repro.models import transformer as tf
from repro.models.eingraphs import plan_for
from repro.models.policy import manual_policy


def main() -> None:
    cfg = reduced(get_config("llama-7b"))
    mesh = make_mesh((2, 4), ("data", "model"))
    for seq in (512, 2048, 8192):
        shape = ShapeConfig("mem", "prefill", seq, 8)
        _, _, auto = plan_for(cfg, shape, mesh_axes_dict(mesh))
        for name, pol in (("eindecomp", auto),
                          ("data_parallel", manual_policy({"b": "data"}))):
            params = tf.init_params(cfg, abstract=True)
            pshard = tf.param_shardings(cfg, pol, mesh)
            params = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                params, pshard)
            batch = tf.input_specs(cfg, shape)
            step = steps_mod.make_prefill_step(cfg, policy=pol, mesh=mesh)
            with mesh:
                compiled = jax.jit(step).lower(params, batch).compile()
            ma = compiled.memory_analysis()
            total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            print(f"MEMROW exp4_mem_s{seq}_{name} {total / 1e6:.3f}")


if __name__ == "__main__":
    main()
