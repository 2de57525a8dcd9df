"""Cloud-storage environment — the analogue of the reference's HDFS/DBFS
environments (core/environment/hopsworks.py:33, databricks.py:23).

Backed by ``fsspec``: the filesystem protocol comes from the root URL's
scheme (``gs://`` in production, ``memory://`` in tests — which is how this
class is exercised for real without a bucket). Raises a
clear error at first use when the protocol's driver isn't importable, so
local development never needs gcsfs.
"""

from __future__ import annotations

import posixpath
from typing import List, Optional

from maggy_tpu.core.env.base import BaseEnv


def _fs(protocol: str):
    try:
        import fsspec

        return fsspec.filesystem(protocol)
    except Exception as e:
        raise RuntimeError(
            f"Cloud environment requires fsspec with the {protocol!r} driver "
            "(gcsfs for gs://); install it or use a local MAGGY_TPU_LOG_ROOT."
        ) from e


class GcsEnv(BaseEnv):
    def __init__(self, root: Optional[str] = None):
        super().__init__(root or "gs://maggy-tpu-experiments")
        self.protocol = self.root.split("://", 1)[0] if "://" in self.root else "gs"
        self._fs = None

    @property
    def fs(self):
        if self._fs is None:
            self._fs = _fs(self.protocol)
        return self._fs

    def exists(self, path: str) -> bool:
        return self.fs.exists(path)

    def mkdir(self, path: str) -> None:
        self.fs.makedirs(path, exist_ok=True)

    def delete(self, path: str, recursive: bool = False) -> None:
        if self.fs.exists(path):
            self.fs.rm(path, recursive=recursive)

    def open_file(self, path: str, mode: str = "r"):
        # BaseEnv.dump/load_json work unchanged through this override.
        return self.fs.open(path, mode)

    def listdir(self, path: str) -> List[str]:
        # fs.ls raises FileNotFoundError (an OSError) for missing paths —
        # exactly what callers catch; no extra exists() round-trip
        return sorted(
            posixpath.basename(p) for p in self.fs.ls(path, detail=False)
        )

    def _atomic_dump(self, data, path: str) -> None:
        # an object-store PUT is atomic at the object level: readers see the
        # old object or the new one, never a partial write — no rename dance
        self.dump(data, path)

    def experiment_dir(self, app_id: str, run_id: int) -> str:
        d = posixpath.join(self.root, app_id, str(run_id))
        self.mkdir(d)
        return d

    def trial_dir(self, app_id: str, run_id: int, trial_id: str) -> str:
        d = posixpath.join(self.experiment_dir(app_id, run_id), trial_id)
        self.mkdir(d)
        return d
