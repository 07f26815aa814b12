"""UDP -> disk baseband recorder (ref: src/baseband_receiver.cpp:59-87:
composite_pipe of udp receive + cast + write, no device processing)."""

from __future__ import annotations

import sys

from srtb_tpu.config import Config
from srtb_tpu.io.udp import UdpReceiverSource
from srtb_tpu.utils.logging import log
from srtb_tpu.utils.termination import install_termination_handler


def main(argv=None) -> int:
    install_termination_handler()
    cfg = Config.from_args(argv)
    src = UdpReceiverSource(cfg)
    path = cfg.baseband_output_file_prefix + "recorded.bin"
    n = 0
    # ordered async appends through the native writer pool so disk
    # latency never blocks the UDP drain loop (single thread = in-order)
    from srtb_tpu.io.native_writer import AsyncWriterPool
    with AsyncWriterPool(n_threads=1) as pool:
        try:
            for seg in src:
                pool.submit(path, seg.data, append=True)
                n += 1
                # fail fast on disk errors rather than draining UDP for
                # hours while appends silently fail
                pool.raise_new_errors(f"append to {path}")
                log.debug(f"[baseband_receiver] segment {n}, counter "
                          f"{seg.udp_packet_counter}")
        except KeyboardInterrupt:
            pass
        finally:
            src.close()
            pool.drain()
            pool.raise_new_errors(f"append to {path}")
    log.info(f"[baseband_receiver] wrote {n} segments to {path}; "
             f"lost {src.receiver.lost_packets} packets")
    return 0


if __name__ == "__main__":
    sys.exit(main())
