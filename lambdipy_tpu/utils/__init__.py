"""Shared utilities: timing, structured logging, filesystem helpers, hashing."""

from lambdipy_tpu.utils.timing import StageTimer
from lambdipy_tpu.utils.logs import get_logger
from lambdipy_tpu.utils.fsutil import (
    atomic_write_text,
    copy_tree,
    dir_size,
    hash_file,
    sha256_file,
    walk_files,
)

__all__ = [
    "StageTimer",
    "get_logger",
    "atomic_write_text",
    "copy_tree",
    "dir_size",
    "hash_file",
    "sha256_file",
    "walk_files",
]
