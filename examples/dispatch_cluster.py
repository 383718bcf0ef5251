"""The paper's technique doing its production job: ESDP gang-dispatches the
assigned (arch × shape) workloads onto a heterogeneous TPU fleet whose
service rates come from the compiled dry-run rooflines, fluctuate, and
degrade mid-run (straggler brownout) — ESDP learns and routes around it.

    PYTHONPATH=src python examples/dispatch_cluster.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.sched import ClusterSim, JobType, Slice, build_instance, rate_matrix


def main():
    enable_compile_cache()
    slices = [
        Slice("pod-a", "v5e", 256, 32, 4),
        Slice("pod-b", "v5e", 256, 32, 4),
        Slice("pod-c", "v5e", 512, 64, 8),
        Slice("pod-d", "v5p", 256, 32, 4),
    ]
    jobs = [
        JobType("qwen2.5:train", "qwen2.5-32b", "train_4k", ("v5e", "v5p"),
                256, 32, 4, value_rate=1.0),
        JobType("deepseek:decode", "deepseek-v3-671b", "decode_32k",
                ("v5e", "v5p"), 256, 32, 4, value_rate=1.5),
        JobType("mamba2:long", "mamba2-2.7b", "long_500k", ("v5e",),
                256, 32, 4, value_rate=0.8),
        JobType("gemma3:prefill", "gemma3-27b", "prefill_32k", ("v5e",),
                256, 32, 4, value_rate=0.9),
        JobType("whisper:train", "whisper-medium", "train_4k", ("v5p",),
                256, 32, 4, value_rate=0.4),
    ]
    rates = rate_matrix(jobs, slices)
    inst, _ = build_instance(slices, jobs, rates, seed=0)
    print(f"cluster instance: {inst.n_ports} job types × "
          f"{inst.n_servers} slices, {inst.n_edges} channels")

    T = 800
    R = len(slices)

    def brownout(t0):   # pod-b at 40% speed in the middle third
        s = np.ones(R, np.float32)
        if T // 3 < t0 < 2 * T // 3:
            s[1] = 0.4
        return s

    for pol in ("esdp", "hswf", "lcf", "lwtf"):
        out = ClusterSim(inst, T, speed_fn=brownout, seed=7).run(
            pol, tiebreak=0.0)
        print(f"{pol:5s} ASW={out.asw:8.1f} cumRegret={out.cum_regret[-1]:8.1f}")

    out = ClusterSim(inst, T, speed_fn=brownout, seed=7).run("esdp")
    mid = slice(T // 3, 2 * T // 3)
    print("pod-b dispatch share: before brownout "
          f"{out.dispatch_share[:T // 3, 1].mean():.3f}, during "
          f"{out.dispatch_share[mid, 1].mean():.3f}, after "
          f"{out.dispatch_share[2 * T // 3:, 1].mean():.3f}")


if __name__ == "__main__":
    main()
