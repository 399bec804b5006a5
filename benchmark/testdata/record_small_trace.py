"""Record a small TPU trace for benchmark/testdata (run on the chip)."""
import os, time, glob, shutil, jax, jax.numpy as jnp
out = "chiprun_out/testdata"; shutil.rmtree("/tmp/_rec", ignore_errors=True)
f = jax.jit(lambda x: (x @ x) * 0.5 + 1.0)
x = jnp.ones((1024, 1024), jnp.bfloat16); f(x).block_until_ready()
opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0; opts.host_tracer_level = 2
jax.profiler.start_trace("/tmp/_rec", profiler_options=opts)
with jax.profiler.TraceAnnotation("bench_window"):
    for i in range(3):
        with jax.profiler.TraceAnnotation("input"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("fit_step"):
            y = f(x)
        with jax.profiler.TraceAnnotation("readback"):
            float(y[0, 0])
jax.profiler.stop_trace()
p = glob.glob("/tmp/_rec/**/*.xplane.pb", recursive=True)[0]
os.makedirs(out, exist_ok=True); shutil.copy(p, out + "/small_tpu.xplane.pb")
print("trace bytes", os.path.getsize(p))
from jax.profiler import ProfileData
pd = ProfileData.from_file(p)
for pl in pd.planes:
    print("PLANE", pl.name)
    for ln in pl.lines:
        evs = list(ln.events)
        print("  LINE", ln.name, len(evs))
        for ev in evs[:8]:
            print("     ", ev.name[:70], ev.start_ns, ev.duration_ns)
