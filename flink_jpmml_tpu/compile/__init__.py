"""PMML IR -> JAX lowering (SURVEY.md section 8 step 2): the heart of the framework."""

from flink_jpmml_tpu.compile.cachedir import configure_compile_cache

# every jit in the package lives under, or imports, this package: placing
# the persistent compile cache here places it before the first compile
configure_compile_cache()

from flink_jpmml_tpu.compile.compiler import CompiledModel, compile_pmml  # noqa: E402,F401
from flink_jpmml_tpu.compile.common import ModelOutput  # noqa: E402,F401
