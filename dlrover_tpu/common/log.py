"""Shared logger. Reference parity: dlrover/python/common/log.py."""

import functools
import logging
import os
import sys

_FORMAT = (
    "[%(asctime)s] [%(levelname)s] "
    "[%(filename)s:%(lineno)d:%(funcName)s] %(message)s"
)


def _build_logger() -> logging.Logger:
    logger = logging.getLogger("dlrover_tpu")
    if logger.handlers:
        return logger
    level = os.environ.get("DLROVER_TPU_LOG_LEVEL", "INFO").upper()
    logger.setLevel(getattr(logging, level, logging.INFO))
    handler = logging.StreamHandler(sys.stderr)
    handler.setFormatter(logging.Formatter(_FORMAT))
    logger.addHandler(handler)
    logger.propagate = False
    return logger


default_logger = _build_logger()


@functools.lru_cache(maxsize=None)
def warning_once(msg: str, *args) -> None:
    """Log a warning the first time this exact (msg, args) is seen —
    for decisions taken at trace time, which would otherwise repeat
    on every retrace (args must be hashable)."""
    default_logger.warning(msg, *args)
