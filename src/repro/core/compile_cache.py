"""CompileCache: the TPU-world answer to the paper's 'Python import problem'.

Paper §4.2 / Fig. 4: at 1000 MPI processes, every process imports thousands of
small Python files from a parallel FS -> ~30 min of startup. Containers fix it
because the image is ONE large file mounted per node.

The multi-pod JAX analog: every *host* in a 1000-host job traces, lowers and
compiles the train step -- minutes of redundant work per host, identical on
all of them. The fix is the same shape as the paper's: persist the artifact
once, keyed by content hash, and have every other host load one big file.

Cache levels (best effort, graceful degradation):

  L1  serialized compiled executable (``jax.experimental.serialize_executable``)
      -> deserialize_and_load skips trace+lower+compile entirely;
  L2  StableHLO text of the lowered module
      -> skips trace+lower (the Python-heavy part), recompiles natively;
  L0  miss -> full trace+lower+compile, then populate L1+L2.

Keys: sha256 over (image digest, step kind, mesh fingerprint, abstract input
signature, jax version, backend, device kind, digest of this package's
sources) -- the exact analog of an image digest pinning a bit-exact
environment. A key never collides across meshes, chips, framework versions or
code revisions, so a cache is safely shareable cluster-wide (the paper's
registry role) and survives from one run to the next at a fixed path
(``resolve_cache_dir``).
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import pickle
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable

import jax
from jax.experimental import serialize_executable as _se

_PACKAGE = Path(__file__).resolve().parents[1]     # src/repro
CHECKOUT = _PACKAGE.parents[1]


def resolve_cache_dir() -> Path:
    """The one place a Runtime's CompileCache lives.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory; JAX reads
    the same variable for its own persistent cache, so nothing is set here.
    Otherwise the cache lives at ``<checkout>/.stevedore/compile-cache`` and
    JAX's persistent cache is pointed there too. The path never depends on
    the runtime root, a temporary name, the pid or the time: a cache whose
    directory moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    path = CHECKOUT / ".stevedore" / "compile-cache"
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path


@functools.cache
def _code_digest() -> str:
    """sha256 over the package's sources. A persistent cache outlives the
    code that filled it; keying on the sources keeps an executable traced
    from older code from answering for newer code."""
    h = hashlib.sha256()
    for f in sorted(_PACKAGE.rglob("*.py")):
        h.update(str(f.relative_to(_PACKAGE)).encode())
        h.update(f.read_bytes())
    return h.hexdigest()


def mesh_fingerprint(mesh: jax.sharding.Mesh) -> str:
    return json.dumps(
        {"axes": list(mesh.axis_names), "shape": [int(s) for s in mesh.devices.shape],
         "ndev": int(mesh.devices.size)},
        sort_keys=True,
    )


def abstract_signature(args_tree: Any) -> str:
    leaves, treedef = jax.tree.flatten(args_tree)
    sig = [
        (list(map(int, getattr(l, "shape", ()))), str(getattr(l, "dtype", type(l).__name__)))
        for l in leaves
    ]
    return json.dumps({"tree": str(treedef), "leaves": sig})


@dataclass
class CacheStats:
    hits_l1: int = 0
    hits_l2: int = 0
    misses: int = 0
    last_level: str = ""
    last_seconds: float = 0.0


class CompileCache:
    def __init__(self, root: str | os.PathLike):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.stats = CacheStats()

    # -- keying --------------------------------------------------------------
    def key(self, *, image_digest: str, step_kind: str, mesh: jax.sharding.Mesh,
            args_tree: Any) -> str:
        body = json.dumps(
            {
                "image": image_digest,
                "step": step_kind,
                "mesh": mesh_fingerprint(mesh),
                "sig": abstract_signature(args_tree),
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "device_kind": jax.devices()[0].device_kind,
                "code": _code_digest(),
            },
            sort_keys=True,
        )
        return hashlib.sha256(body.encode()).hexdigest()

    def _paths(self, key: str) -> tuple[Path, Path, Path]:
        return (
            self.root / f"{key}.exec",       # L1: pickled serialized executable
            self.root / f"{key}.stablehlo",  # L2: lowered module text
            self.root / f"{key}.meta.json",
        )

    # -- main entry ------------------------------------------------------------
    def get_or_build(
        self,
        key: str,
        lower_fn: Callable[[], Any],
        *,
        want_executable: bool = True,
    ):
        """Return a compiled executable for ``key``.

        ``lower_fn()`` must return a ``jax.stages.Lowered``. On a miss we
        lower+compile and persist both cache levels.
        """
        p_exec, p_hlo, p_meta = self._paths(key)

        # L1: full executable
        if want_executable and p_exec.exists():
            t0 = time.perf_counter()
            try:
                payload = pickle.loads(p_exec.read_bytes())
                # load onto the devices it was compiled for: by default JAX
                # loads onto every device of the host, which a step built on
                # a sub-mesh (one chip of four) cannot run on
                by_id = {d.id: d for d in jax.devices()}
                compiled = _se.deserialize_and_load(
                    payload["serialized"], payload["in_tree"],
                    payload["out_tree"],
                    execution_devices=[by_id[i] for i in payload["devices"]])
                self.stats.hits_l1 += 1
                self.stats.last_level = "L1"
                self.stats.last_seconds = time.perf_counter() - t0
                return compiled
            except Exception:
                p_exec.unlink(missing_ok=True)  # stale/incompatible: fall through

        t0 = time.perf_counter()
        lowered = lower_fn()
        compiled = lowered.compile()
        elapsed = time.perf_counter() - t0
        self.stats.misses += 1
        self.stats.last_level = "L0"
        self.stats.last_seconds = elapsed

        # populate caches (best effort)
        try:
            _atomic_bytes(p_hlo, lowered.as_text().encode())
        except Exception:
            pass
        try:
            serialized, in_tree, out_tree = _se.serialize(compiled)
            devices = [d.id for d in
                       compiled.runtime_executable().local_devices()]
            _atomic_bytes(
                p_exec,
                pickle.dumps(
                    {"serialized": serialized, "in_tree": in_tree,
                     "out_tree": out_tree, "devices": devices}
                ),
            )
        except Exception:
            pass
        _atomic_bytes(
            p_meta,
            json.dumps(
                {"built_seconds": elapsed, "jax": jax.__version__,
                 "backend": jax.default_backend()}
            ).encode(),
        )
        return compiled

    def lowered_text(self, key: str) -> str | None:
        """L2 read: the persisted StableHLO (for offline roofline analysis)."""
        p = self._paths(key)[1]
        return p.read_text() if p.exists() else None

    def has(self, key: str) -> bool:
        p_exec, p_hlo, _ = self._paths(key)
        return p_exec.exists() or p_hlo.exists()

    def evict(self, key: str) -> None:
        for p in self._paths(key):
            p.unlink(missing_ok=True)


def _atomic_bytes(path: Path, data: bytes) -> None:
    """Write through a temporary file of its own in the same directory, then
    rename: processes sharing a cache (fabric workers, test workers) never
    interleave their bytes, and a reader sees a whole file or none."""
    fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=f".{path.name}.",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        Path(tmp).unlink(missing_ok=True)
        raise
