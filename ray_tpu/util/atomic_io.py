"""The ONE atomic-write implementation (durability contract, RTA009).

Eight modules used to hand-roll some prefix of the crash-safe write
chain — temp file → flush → ``os.fsync`` → ``os.replace`` →
directory fsync — and several skipped the fsyncs: a host crash could
publish a rename pointing at unwritten data blocks, or a directory
entry that never made it to disk, on the exact files the recovery
layer trusts (checkpoints, stream snapshots, experiment state). This
module centralizes the chain; the static
analyzer's RTA009 rule flags any ``os.replace`` outside it, so the
discipline can no longer regress one call site at a time.

``Algorithm._atomic_write`` / ``Algorithm._fsync_dir`` remain as
thin delegates for existing callers.
"""

from __future__ import annotations

import os
import tempfile
from typing import Callable

__all__ = ["atomic_write", "fsync_dir"]


# ray-tpu: atomic-writer
def atomic_write(
    path: str,
    write_fn: Callable,
    *,
    sync_dir: bool = True,
) -> None:
    """Write ``path`` through a same-directory temp file so a crash
    mid-save leaves either the old complete file or the new complete
    file — never a truncated one.

    fsync before the rename (the replace must not be reordered ahead
    of the data blocks), then — unless ``sync_dir=False`` — fsync the
    parent DIRECTORY: the rename itself lives in the directory inode,
    and without this a host crash can leave an entry pointing at the
    old (or no) file even though the data blocks hit disk. Pass
    ``sync_dir=False`` only when the caller batches several writes
    and issues one :func:`fsync_dir` at the end (the
    ``save_checkpoint`` shape).
    """
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(path) or ".",
        prefix=os.path.basename(path) + ".tmp.",
    )
    try:
        with os.fdopen(fd, "wb") as f:
            write_fn(f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    if sync_dir:
        fsync_dir(os.path.dirname(path) or ".")


# ray-tpu: atomic-writer
def fsync_dir(path: str) -> None:
    """Flush a directory's entries (renames/unlinks) to disk. Best
    effort: platforms without directory fds are a no-op."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
