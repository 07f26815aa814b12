"""Config / expression-parsing tests (ref oracle: program_options.hpp
expression handling; example config userspace/srtb_config_1644-4559.cfg)."""

import os
import tempfile

import pytest

from srtb_tpu.config import Config
from srtb_tpu.utils.expression import parse_expression, parse_number


def test_expressions():
    assert parse_expression("2 ** 30") == 2 ** 30
    assert parse_expression("1405 + (64 / 2)") == 1437.0
    assert parse_expression("128 * 1e6") == 128e6
    assert parse_number("-478.80") == -478.80
    assert parse_number("2 ** 11") == 2048


def test_config_file_roundtrip():
    text = """
# example config file (mirrors srtb_config_1644-4559.cfg)
baseband_input_count = 2 ** 20
spectrum_channel_count = 2 ** 11
log_level = 4
mitigate_rfi_average_method_threshold = 1.5
signal_detect_max_boxcar_length = 256
baseband_input_bits = 2
dm = -478.80
baseband_reserve_sample = 0
baseband_freq_low = 1405 + (64 / 2)
baseband_bandwidth = -64
baseband_sample_rate = 128 * 1e6
mitigate_rfi_freq_list = 1418-1422
"""
    with tempfile.NamedTemporaryFile("w", suffix=".cfg", delete=False) as f:
        f.write(text)
        path = f.name
    try:
        cfg = Config()
        cfg.load_file(path)
    finally:
        os.unlink(path)
    assert cfg.baseband_input_count == 2 ** 20
    assert cfg.spectrum_channel_count == 2048
    assert cfg.baseband_input_bits == 2
    assert cfg.dm == -478.80
    assert cfg.baseband_reserve_sample is False
    assert cfg.baseband_freq_low == 1437.0
    assert cfg.baseband_bandwidth == -64
    assert cfg.baseband_sample_rate == 128e6
    assert cfg.mitigate_rfi_freq_list == "1418-1422"


def test_cli_precedence():
    cfg = Config.from_args(["--dm=10.5", "--baseband-input-count", "2**16"])
    assert cfg.dm == 10.5
    assert cfg.baseband_input_count == 65536


def test_reference_config_key_parity():
    """Every runtime option of the reference (config.hpp srtb::configs +
    program_options.hpp extras) exists under the same name, so reference
    users can bring their .cfg files across unchanged."""
    from dataclasses import fields
    reference_keys = {
        # ref: userspace/include/srtb/config.hpp:80-249
        "baseband_bandwidth", "baseband_format_type", "baseband_freq_low",
        "baseband_input_bits", "baseband_input_count",
        "baseband_output_file_prefix", "baseband_reserve_sample",
        "baseband_sample_rate", "baseband_write_all", "config_file_name",
        "dm", "fft_fftw_wisdom_path", "gui_enable", "gui_pixmap_height",
        "gui_pixmap_width", "input_file_offset_bytes", "input_file_path",
        "mitigate_rfi_average_method_threshold", "mitigate_rfi_freq_list",
        "mitigate_rfi_spectral_kurtosis_threshold",
        "signal_detect_channel_threshold", "signal_detect_max_boxcar_length",
        "signal_detect_signal_noise_threshold", "spectrum_channel_count",
        "spectrum_sum_count", "thread_query_work_wait_time",
        # ref: program_options.hpp (CLI-only options)
        "udp_receiver_address", "udp_receiver_port",
        "udp_receiver_cpu_preferred", "log_level",
    }
    ours = {f.name for f in fields(Config)}
    missing = reference_keys - ours
    assert not missing, f"reference options without parity: {missing}"


@pytest.mark.parametrize("route", ["file", "command_line"])
@pytest.mark.parametrize("key", ["front_fuse", "warp_drive"])
def test_an_option_that_does_not_exist_warns_and_sets_nothing(
        tmp_path, key, route):
    """A cfg line or a command-line option the program does not have
    (a misspelling; `front_fuse`, retired in PR 50 with the kernels it
    chose) is the unknown-option warning, with the file and line where
    there is one, and the rest of the options are read."""
    import io

    from srtb_tpu.utils.logging import log

    stream, log.stream = log.stream, io.StringIO()
    try:
        if route == "file":
            path = tmp_path / "retired.cfg"
            path.write_text(f"dm = 12.5\n{key} = on\nlog_level = 3\n")
            cfg = Config()
            cfg.load_file(str(path))
            want = f"{path}:2: unknown option {key!r}"
        else:
            cfg = Config.from_args([
                "--dm=12.5", f"--{key}=on", "--log_level=3",
                f"--config_file_name={tmp_path / 'none.cfg'}"])
            want = f"unknown command-line option --{key}"
        said = log.stream.getvalue()
    finally:
        log.stream = stream
    assert want in said and said.count("unknown") == 1
    assert cfg.dm == 12.5 and cfg.log_level == 3
    assert not hasattr(cfg, key)


def test_front_fuse_is_no_field_of_config():
    """`Config(front_fuse=...)` is the `TypeError` of a field that does
    not exist: the option went with the kernels no chip compiled."""
    with pytest.raises(TypeError, match="front_fuse"):
        Config(front_fuse="on")
