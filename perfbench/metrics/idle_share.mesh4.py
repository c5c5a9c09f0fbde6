"""idle_share.mesh4: share of the traced window in which no device ran an
operation, in %, in the four-chip ad-hoc cells: ``idle_share.query``'s
reading (its reader, loaded by path), where each chip's busy time is the
union of its operations' intervals and the share is taken from their mean
over the four chips (``tracing.reduce``)."""
from pathlib import Path

from harness import load_module

read = load_module(Path(__file__).with_name("idle_share.query.py")).read
