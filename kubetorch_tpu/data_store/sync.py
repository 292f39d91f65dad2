"""Delta tree sync — the rsync replacement.

The reference shells out to the rsync binary (``data_store/rsync_client.py``);
this environment has none, and a TPU-native framework shouldn't depend on one.
``sync_tree`` copies only files whose (size, mtime) or content hash changed
and deletes files absent from the source — rsync's behavior for the code-sync
use case. Hashing uses the native C scanner
(``kubetorch_tpu/data_store/native``) when built, else hashlib.

The same scan powers the HTTP delta protocol in ``store_server.py``: client
sends its manifest, server answers with needed paths, client uploads only
those.
"""

from __future__ import annotations

import fnmatch
import hashlib
import os
import shutil
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

DEFAULT_EXCLUDES = (
    ".git", "__pycache__", "*.pyc", ".venv", "venv", "node_modules",
    ".pytest_cache", ".mypy_cache", "*.egg-info", ".DS_Store",
)


def _excluded(rel: str, excludes: Iterable[str]) -> bool:
    parts = rel.split(os.sep)
    for pattern in excludes:
        if any(fnmatch.fnmatch(part, pattern) for part in parts):
            return True
        if fnmatch.fnmatch(rel, pattern):
            return True
    return False


def file_hash(path: Path) -> str:
    """Content hash; native scanner (xxh64-style), or blake2b-128 on a
    machine with no toolchain to build it. The two never compare equal, so
    a tree hashed one way re-syncs in full against one hashed the other:
    the failed build is logged where it happens, once."""
    from kubetorch_tpu.data_store import native

    try:
        return native.hash_file(str(path))
    except RuntimeError:             # the build failed; nothing else
        pass
    h = hashlib.blake2b(digest_size=16)
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def scan_tree(
    root: Path,
    excludes: Iterable[str] = DEFAULT_EXCLUDES,
    with_hash: bool = False,
) -> Dict[str, Tuple[int, int, str]]:
    """rel_path -> (size, mtime_ns, hash-or-'')"""
    manifest: Dict[str, Tuple[int, int, str]] = {}
    root = root.resolve()
    for dirpath, dirnames, filenames in os.walk(root):
        rel_dir = os.path.relpath(dirpath, root)
        dirnames[:] = [
            d for d in dirnames
            if not _excluded(os.path.join(rel_dir, d).lstrip("./"), excludes)]
        for fname in filenames:
            rel = os.path.normpath(os.path.join(rel_dir, fname)).lstrip("./")
            if _excluded(rel, excludes):
                continue
            full = Path(dirpath) / fname
            try:
                stat = full.stat()
            except OSError:
                continue
            digest = file_hash(full) if with_hash else ""
            manifest[rel] = (stat.st_size, stat.st_mtime_ns, digest)
    return manifest


def diff_manifests(
    src: Dict[str, Tuple[int, int, str]],
    dest: Dict[str, Tuple[int, int, str]],
    use_hash: bool = False,
) -> Tuple[List[str], List[str]]:
    """(paths to copy, paths to delete)."""
    to_copy = []
    for rel, (size, mtime, digest) in src.items():
        have = dest.get(rel)
        if have is None:
            to_copy.append(rel)
        elif use_hash and digest and have[2]:
            if digest != have[2]:
                to_copy.append(rel)
        elif (size, mtime) != (have[0], have[1]):
            to_copy.append(rel)
    to_delete = [rel for rel in dest if rel not in src]
    return to_copy, to_delete


def sync_tree(
    src: Path,
    dest: Path,
    excludes: Iterable[str] = DEFAULT_EXCLUDES,
    delete: bool = True,
    use_hash: bool = False,
) -> Tuple[int, int]:
    """Make ``dest`` mirror ``src``. Returns (files copied, files deleted)."""
    src, dest = Path(src), Path(dest)
    if not src.is_dir():
        raise ValueError(f"{src} is not a directory")
    dest.mkdir(parents=True, exist_ok=True)
    src_manifest = scan_tree(src, excludes, with_hash=use_hash)
    dest_manifest = scan_tree(dest, excludes, with_hash=use_hash)
    to_copy, to_delete = diff_manifests(src_manifest, dest_manifest, use_hash)
    for rel in to_copy:
        target = dest / rel
        target.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy2(src / rel, target)
    if delete:
        for rel in to_delete:
            try:
                (dest / rel).unlink()
            except OSError:
                pass
    return len(to_copy), len(to_delete)
