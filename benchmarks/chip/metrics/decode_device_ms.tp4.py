"""Mean device milliseconds of one tensor-parallel decode call on chip 0:
``decode_device_ms``'s reading (each ``jit_serve_decode`` event on the
chip's ``XLA Modules`` line) under this cell's name."""

import os

import harness

read = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "decode_device_ms.py"), "metric_decode_device_ms_tp4").read
