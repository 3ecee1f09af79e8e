"""Process-wide logger (reference: dlrover/python/common/log.py)."""

import logging
import sys

from dlrover_tpu.common.constants import ConfigKey, env_str

_FORMAT = (
    "[%(asctime)s] [%(levelname)s] "
    "[%(filename)s:%(lineno)d:%(funcName)s] %(message)s"
)


def _build_logger() -> logging.Logger:
    logger = logging.getLogger("dlrover_tpu")
    if logger.handlers:
        return logger
    level_name = env_str(ConfigKey.LOG_LEVEL, "INFO").upper()
    logger.setLevel(getattr(logging, level_name, logging.INFO))
    handler = logging.StreamHandler(sys.stderr)
    handler.setFormatter(logging.Formatter(_FORMAT))
    logger.addHandler(handler)
    logger.propagate = False
    return logger


default_logger = _build_logger()
logger = default_logger


_logged_once = set()


def log_once(msg: str, *args) -> None:
    """INFO-log a (message, args) pair the first time this process sees
    it — for choices made at trace time (kernel vs fallback), which would
    otherwise repeat on every retrace."""
    if (msg, args) not in _logged_once:
        _logged_once.add((msg, args))
        logger.info(msg, *args)
