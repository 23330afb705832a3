"""Test harness: force an 8-device virtual CPU platform BEFORE jax is imported.

Mirrors the reference's trick of faking torch.distributed (SURVEY.md §4): the multi-chip
sharding paths are validated on a host-only mesh, no TPUs required.

The suite spends most of its time in XLA compiling some thousands of small CPU
programs, and little running them: LLVM's optimisation of the code XLA emits
(``--xla_backend_optimization_level``) is left at 0, which takes about a tenth off
that compile time and leaves every result the tests read within what they hold
it to, bit-for-bit parities included.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    xla_flags += " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in xla_flags:
    xla_flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = xla_flags.strip()

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402

from replay_tpu.data import Dataset, FeatureHint, FeatureInfo, FeatureSchema, FeatureType  # noqa: E402


@pytest.fixture
def interactions_pandas() -> pd.DataFrame:
    return pd.DataFrame(
        {
            "user_id": [0, 0, 0, 1, 1, 2, 2, 2, 2, 3],
            "item_id": [0, 1, 2, 0, 2, 3, 1, 2, 0, 3],
            "rating": [1.0, 2.0, 3.0, 4.0, 5.0, 1.0, 2.0, 3.0, 4.0, 5.0],
            "timestamp": [0, 1, 2, 3, 4, 5, 6, 7, 8, 9],
        }
    )


@pytest.fixture
def feature_schema() -> FeatureSchema:
    return FeatureSchema(
        [
            FeatureInfo("user_id", FeatureType.CATEGORICAL, FeatureHint.QUERY_ID),
            FeatureInfo("item_id", FeatureType.CATEGORICAL, FeatureHint.ITEM_ID),
            FeatureInfo("rating", FeatureType.NUMERICAL, FeatureHint.RATING),
            FeatureInfo("timestamp", FeatureType.NUMERICAL, FeatureHint.TIMESTAMP),
        ]
    )


@pytest.fixture
def dataset(feature_schema, interactions_pandas) -> Dataset:
    return Dataset(feature_schema=feature_schema, interactions=interactions_pandas)


@pytest.fixture
def rng() -> np.random.Generator:
    return np.random.default_rng(0)


def pytest_collection_modifyitems(config, items):
    """Auto-mark tests: ``jax`` for device-touching paths, ``core`` for the rest.

    Mirrors the reference's core/torch marker split (its CI runs them as separate
    job families) so the fast dataframe tier stays seconds-fast.
    """
    import pytest as _pytest

    jax_paths = ("tests/nn", "tests/parallel", "tests/models/nn", "test_builder", "test_train")
    for item in items:
        if item.get_closest_marker("jax") or item.get_closest_marker("core"):
            continue  # explicitly marked
        path = str(item.fspath)
        if any(fragment in path for fragment in jax_paths):
            item.add_marker(_pytest.mark.jax)
        else:
            item.add_marker(_pytest.mark.core)


@pytest.fixture(scope="session", autouse=True)
def shared_bytecode_cache(tmp_path_factory):
    """Python's bytecode cache, in a directory of this session's own, for the
    processes the tests start (servers, killed workers, ranks): where the
    environment forbids writing bytecode, each of them compiles jax, flax and
    this package from source again, some seconds a process. With a shared
    prefix the first child writes the bytecode and the others read it; nothing
    is written beside the sources."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("PYTHONPYCACHEPREFIX", str(tmp_path_factory.mktemp("pycache")))
        patch.delenv("PYTHONDONTWRITEBYTECODE", raising=False)
        yield


@pytest.fixture(scope="session", autouse=True)
def shared_compile_cache(tmp_path_factory):
    """JAX's persistent compilation cache, in a directory of this session's own,
    for every test: the suite builds hundreds of trainers of a few tiny models,
    each with jitted closures of its own, so every one traces and lowers the same
    program again; with the cache XLA compiles it once a session and the others
    load it. Tracing still happens, so ``compile_tracker`` counts what it counted.
    The processes the tests start (a server, a killed worker) are pointed at the
    same directory through the environment, so the second server of a model
    loads what the first compiled.
    A test that asserts on the cache's own settings sets and restores its own
    (``tests/ops/test_tpu_compile.py`` turns the cache off around its compiles)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = tmp_path_factory.mktemp("jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # JAX looks at the directory once, at the process's first compile: have it
    # look again in case something compiled while the tests were collected
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
        patch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        patch.setenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
        yield


class SharedPrograms:
    """Trainers of ONE configuration run the same two jitted programs.

    A test that builds three trainers of one tiny model to compare their results
    traces and lowers ``train_step`` and ``train_scan`` three times, which is most
    of its run time. ``adopt`` hands a new trainer the jitted functions of the
    first trainer adopted under the same ``key``: the model, loss, optimizer,
    mesh, precision and health are the same, so the programs are, and everything
    a trainer keeps to itself (state, history, rng, sinks) stays its own. A module
    with several configurations gives each a ``key`` (whatever tells them apart:
    the mesh, the attention route, the precision). Not for a trainer whose
    ``compile_tracker`` counts are asserted, whose programs a test rebuilds (an
    LR backoff, a vocabulary resize), or whose configuration no key names: those
    keep their own.
    """

    def __init__(self) -> None:
        self.programs = {}  # key -> the first such trainer's (train_step, train_scan)
        self.params = {}  # (the model's repr, the seed) -> its fresh parameters

    def adopt(self, trainer, key=None):
        if key not in self.programs:
            # taken now: what the first trainer does to itself later (an LR
            # backoff rebuilds its programs) is not handed on
            self.programs[key] = (trainer._ensure_train_step(), trainer._ensure_train_scan())
        else:
            trainer._train_step, trainer._train_scan = self.programs[key]
        return self.share_init(trainer)

    def share_init(self, trainer):
        """Every trainer of one model and seed starts from the SAME fresh
        parameters, made once: flax's init dispatched eagerly is some ninety small
        programs a trainer, under ``jax.jit`` it is one, and the trainers that
        follow get a copy (the seed and the model are the same, so the init would
        be)."""
        import jax

        init_state = trainer.init_state
        key = (repr(trainer.model), trainer.seed)

        def init_from_shared(example_batch, params=None):
            if params is None:
                if key not in self.params:
                    self.params[key] = jax.device_get(
                        jax.jit(trainer._init_params)(example_batch)
                    )
                params = self.params[key]
            return init_state(example_batch, params=params)

        trainer.init_state = init_from_shared
        return trainer


@pytest.fixture(scope="module", autouse=True)
def _shared_programs(request):
    """A test module that declares ``PROGRAMS = None`` gets its own
    :class:`SharedPrograms` there before its first test, and lets it (and the
    executables it holds) go after its last."""
    shares = hasattr(request.module, "PROGRAMS")
    if shares:
        request.module.PROGRAMS = SharedPrograms()
    yield
    if shares:
        request.module.PROGRAMS = None
