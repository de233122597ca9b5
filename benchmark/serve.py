"""The system under test, as the child process that alone holds the chip.

Runs the real daemon (gubernator_tpu.daemon, configured by the GUBER_* the
harness put in the environment) and adds the two things only the process that
owns the device can say: what the device is, written to $BENCH_INFO_FILE
before the daemon starts, and its peak memory, added to the same file after
the daemon has stopped on SIGTERM.  The harness, not this file, decides
whether the device will do.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    info_file = os.environ["BENCH_INFO_FILE"]
    import jax
    devs, info = [], {}

    def write():
        with open(info_file + ".tmp", "w") as f:
            json.dump(info, f)
        os.replace(info_file + ".tmp", info_file)

    def report():
        devs.extend(jax.devices())
        info.update(platform=devs[0].platform, kind=devs[0].device_kind,
                    count=len(devs))
        write()

    if os.environ.get("GUBER_MESH_COORDINATOR"):
        # mesh mode: jax.distributed.initialize has to come before the first
        # look at the devices, and the daemon makes that call itself
        from gubernator_tpu.parallel import distributed
        join = distributed.initialize_from_env

        def join_then_report():
            on = join()
            report()
            return on
        distributed.initialize_from_env = join_then_report
    else:
        report()

    from gubernator_tpu import daemon
    daemon.main([])

    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    info["memory_peak_bytes"] = max(peaks)
    write()


if __name__ == "__main__":
    main()
