"""Main pipeline entry point (ref: src/main.cpp:88-333).

Usage:
    python -m srtb_tpu.tools.main --config_file_name srtb_config.cfg \
        [--key value ...]

Input selection follows the reference (main.cpp:241-271): if
``input_file_path`` exists, read from file; otherwise start UDP receivers.
The GUI equivalent (waterfall PNG service) activates with ``gui_enable``.
"""

from __future__ import annotations

import os
import sys

from srtb_tpu.config import Config
from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.utils.logging import log
from srtb_tpu.utils.termination import install_termination_handler


def main(argv=None) -> int:
    install_termination_handler()
    cfg = Config.from_args(argv)
    if cfg.distributed_num_processes > 1:
        from srtb_tpu.parallel.distributed import (
            maybe_initialize_from_config)
        maybe_initialize_from_config(cfg)
    if cfg.fft_fftw_wisdom_path != "off":
        from srtb_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache(cfg.fft_fftw_wisdom_path)
    log.info(f"[main] nsamps_reserved = {dd.nsamps_reserved(cfg)}")
    if cfg.telemetry_journal_path:
        log.info("[main] segment-span journal -> "
                 f"{cfg.telemetry_journal_path} (summarize with "
                 "python -m srtb_tpu.tools.telemetry_report)")

    sinks = None
    waterfall_service = None
    gui_server = None
    if cfg.gui_http_port and not cfg.gui_enable:
        # a live viewer port only makes sense with frames being rendered
        log.info("[main] gui_http_port set: enabling the waterfall service")
        cfg.gui_enable = True
    if cfg.gui_enable:
        from srtb_tpu.gui.waterfall import WaterfallService
        n_spec = cfg.baseband_input_count // 2
        nchan = min(cfg.spectrum_channel_count, n_spec)
        out_dir = os.path.dirname(cfg.baseband_output_file_prefix) or "."
        waterfall_service = WaterfallService(
            cfg, in_freq=nchan, in_time=n_spec // nchan, out_dir=out_dir)
        if cfg.gui_http_port:
            from srtb_tpu.gui.server import WaterfallHTTPServer
            from srtb_tpu.resilience.supervisor import Supervisor
            gui_server = WaterfallHTTPServer(
                out_dir, port=cfg.gui_http_port,
                health_stale_after_s=cfg.health_stale_after_s,
                fleet_store_dir=getattr(cfg, "obs_store_dir", ""),
                # the configured restart budget covers the GUI server
                # too (config.py: supervisor_max_restarts, 0 = give up
                # on the first crash); best-effort, so fatal crashes
                # restart as well — GUI death never ends the run
                supervisor=Supervisor(
                    "gui_server",
                    max_restarts=cfg.supervisor_max_restarts,
                    window_s=cfg.supervisor_window_s,
                    restart_fatal=True)).start()

    if cfg.input_file_path and os.path.exists(cfg.input_file_path):
        source = None  # Pipeline builds the file reader
    elif cfg.input_file_path:
        log.error(f"[main] input file {cfg.input_file_path} not found")
        return 1
    elif len(cfg.udp_receiver_port) > 1:
        from srtb_tpu.io.udp import MultiUdpSource
        source = MultiUdpSource(cfg)
    else:
        from srtb_tpu.io.udp import UdpReceiverSource
        source = UdpReceiverSource(cfg)

    if cfg.dm_list:
        # multi-chip DM-trial search mode
        from srtb_tpu.pipeline.runtime import DMSearchPipeline
        search = DMSearchPipeline(cfg, source=source)
        try:
            stats = search.run()
        finally:
            search.close()
        log.info(f"[main] dm search done: {stats.segments} segments, "
                 f"{stats.signals} with signal; trials in "
                 f"{search.trials_path}")
        return 0

    pipe = Pipeline(cfg, source=source, sinks=sinks)
    if waterfall_service is not None:
        class _Tap:
            def push(self, work, has_signal):
                if work.waterfall is not None:
                    waterfall_service.push(work.waterfall,
                                           work.segment.data_stream_id)
                    waterfall_service.render_pending()
        pipe.sinks.append(_Tap())

    try:
        stats = pipe.run()
    finally:
        pipe.close()
    if gui_server is not None:
        gui_server.stop()
    log.info(f"[main] done: {stats.segments} segments, "
             f"{stats.signals} with signal, "
             f"{stats.msamples_per_sec:.1f} Msamples/s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
