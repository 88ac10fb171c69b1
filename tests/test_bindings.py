"""Frontend binding sources: JVM (jvm-package/) and R (r-package/).

Reference roles: scala-package/ (~37k LoC JVM frontend) and R-package/.
The CI image has neither a JDK nor R, so the build/run tests skip with a
clear reason there — but the source-level consistency checks ALWAYS run:
every Java `native` method must have a matching JNI export (and vice
versa), every R .Call symbol must be registered in mxtpu_r.c, and the C
sources must only reference symbols the native ABIs actually export.
"""
import os
import re
import shutil
import subprocess
import sys
import sysconfig

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JVM = os.path.join(REPO, "jvm-package")
RPKG = os.path.join(REPO, "r-package")


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return f.read()


def test_jni_exports_match_java_natives():
    java = _read(JVM, "src", "main", "java", "org", "apache", "mxtpu",
                 "LibMXTpu.java")
    natives = set(re.findall(r"static native \S+(?:\[\])? (\w+)\(", java))
    assert natives, "no native methods parsed from LibMXTpu.java"
    cc = _read(JVM, "src", "main", "native", "mxtpu_jni.cc")
    exports = set(re.findall(r"Java_org_apache_mxtpu_LibMXTpu_(\w+)\(", cc))
    assert natives == exports, (
        f"JNI mismatch: java-only={sorted(natives - exports)}, "
        f"cc-only={sorted(exports - natives)}")


def test_jni_uses_only_real_abi_symbols():
    """Every MXTpu* symbol the JNI layer calls must exist in the native
    runtimes' sources (catches ABI drift without a JDK)."""
    cc = _read(JVM, "src", "main", "native", "mxtpu_jni.cc")
    used = set(re.findall(r"\b(MXTpu\w+)\(", cc))
    impl = (_read(REPO, "src", "imperative.cc")
            + _read(REPO, "src", "train.cc")
            + _read(REPO, "src", "predict.cc"))
    defined = set(re.findall(r"\b(MXTpu\w+)\(", impl))
    missing = used - defined
    assert not missing, f"JNI references unknown ABI symbols: {sorted(missing)}"


def test_r_call_registration_consistent():
    c = _read(RPKG, "src", "mxtpu_r.c")
    registered = set(re.findall(r'\{"(mxr_\w+)"', c))
    defined = set(re.findall(r"^SEXP (mxr_\w+)\(", c, re.M))
    assert registered == defined, (registered ^ defined)
    r = _read(RPKG, "R", "mxtpu.R")
    called = set(re.findall(r"\.Call\((mxr_\w+)", r))
    assert called <= registered, f"unregistered .Call: {called - registered}"


def test_generated_r_ops_current():
    """The checked-in ops_gen.R must match what the registry produces
    (same content-compare pattern as the JVM generator test)."""
    target = os.path.join(RPKG, "R", "ops_gen.R")
    before = open(target).read()
    try:
        gen = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "gen_r_api.py")],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert gen.returncode == 0, gen.stderr[-800:]
        after = open(target).read()
        assert before == after, "stale ops_gen.R — run tools/gen_r_api.py"
    finally:
        with open(target, "w") as f:
            f.write(before)


def test_r_model_api_surface():
    """model.R must define the FeedForward training frontend (reference
    R-package/R/model.R:470 mx.model.FeedForward.create role)."""
    src = _read(RPKG, "R", "model.R")
    for fn in ("mx.model.FeedForward.create", "mx.symbol.Variable",
               "mx.symbol.FullyConnected", "mx.symbol.Activation",
               "mx.symbol.Convolution", "mx.symbol.Pooling",
               "mx.symbol.Flatten",
               "mx.symbol.SoftmaxOutput", "mx.model.init.params",
               "predict.MXFeedForwardModel", "mx.model.save",
               "mx.model.load", "mx.model.accuracy"):
        assert re.search(rf"^{re.escape(fn)} <- function",
                         src, re.M), f"model.R missing {fn}"


def test_r_frontend_calls_resolve():
    """Every mx.nd.<op> call in model.R and the R examples must be a
    function ops_gen.R actually defines, and every R-exported pattern
    must match at least one definition (catches typos without R)."""
    defined = set(re.findall(r"^(mx\.nd\.\w+) <- function",
                             _read(RPKG, "R", "ops_gen.R"), re.M))
    assert len(defined) > 250, "suspiciously few generated R ops"
    srcs = [_read(RPKG, "R", "model.R")]
    exdir = os.path.join(RPKG, "examples")
    for f in sorted(os.listdir(exdir)):
        if f.endswith(".R"):
            srcs.append(_read(exdir, f))
    for src in srcs:
        used = set(re.findall(r"\b(mx\.nd\.\w+)\(", src))
        used -= {"mx.nd.array", "mx.nd.to.array", "mx.nd.shape"}
        missing = used - defined
        assert not missing, f"R frontend calls unknown ops: {sorted(missing)}"


def test_r_namespace_consistent():
    """NAMESPACE export list must cover the hand-written API and the
    generated/exported patterns must compile against the sources."""
    ns = _read(RPKG, "NAMESPACE")
    hand = _read(RPKG, "R", "mxtpu.R")
    for fn in re.findall(r"^(mx\.[\w.]+) <- function", hand, re.M):
        assert f"export({fn})" in ns or re.search(
            r'exportPattern\("([^"]+)"\)', ns) and any(
            re.match(pat.replace("\\\\", "\\"), fn)
            for pat in re.findall(r'exportPattern\("([^"]+)"\)', ns)), \
            f"NAMESPACE does not export {fn}"


def test_r_uses_only_real_abi_symbols():
    c = _read(RPKG, "src", "mxtpu_r.c")
    used = set(re.findall(r"\b(MXTpuImp\w+)\(", c))
    impl = _read(REPO, "src", "imperative.cc")
    defined = set(re.findall(r"\b(MXTpuImp\w+)\(", impl))
    assert used <= defined, f"R glue references unknown symbols: {used - defined}"


def test_generated_jvm_ops_current():
    """Regenerate and compare CONTENT (not git state, which would flag
    legitimately uncommitted work): the checked-in Ops.java must match
    what the registry produces."""
    target = os.path.join(JVM, "src", "main", "java", "org", "apache",
                          "mxtpu", "Ops.java")
    before = open(target).read()
    try:
        gen = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "gen_jvm_api.py")],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert gen.returncode == 0, gen.stderr[-800:]
        after = open(target).read()
        assert before == after, "stale Ops.java — run tools/gen_jvm_api.py"
    finally:
        # never leave the working tree mutated (a stale file regenerated
        # in-place would make a CI retry pass spuriously)
        with open(target, "w") as f:
            f.write(before)


def _jdk():
    home = os.environ.get("JAVA_HOME")
    if home and os.path.exists(os.path.join(home, "include", "jni.h")):
        return home
    javac = shutil.which("javac")
    if javac:
        home = os.path.dirname(os.path.dirname(os.path.realpath(javac)))
        if os.path.exists(os.path.join(home, "include", "jni.h")):
            return home
    return None


@pytest.mark.skipif(_jdk() is None,
                    reason="no JDK with jni.h in this image (set JAVA_HOME)")
def test_jvm_binding_builds_and_trains():
    from incubator_mxnet_tpu._native import imperative_lib, train_lib

    assert imperative_lib() is not None and train_lib() is not None
    env = dict(os.environ)
    env["JAVA_HOME"] = _jdk()
    build = subprocess.run(["bash", os.path.join(JVM, "build.sh")],
                           capture_output=True, text=True, timeout=600,
                           env=env)
    assert build.returncode == 0, build.stderr[-2000:]
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run(
        [os.path.join(_jdk(), "bin", "java"),
         "-cp", os.path.join(JVM, "target", "mxtpu.jar"),
         "-Djava.library.path=" + os.path.join(JVM, "target"),
         "org.apache.mxtpu.examples.TrainMlp"],
        capture_output=True, text=True, timeout=600, env=env)
    assert run.returncode == 0, (run.stdout[-800:], run.stderr[-1500:])
    assert "TRAINED" in run.stdout
    # Module.fit over an exported .mxt (the scala Module.fit contract):
    # export a tiny trainer artifact, then fit it from the JVM
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        export = subprocess.run(
            [sys.executable, "-c", """
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import deploy, gluon
from incubator_mxnet_tpu.gluon import nn
import sys
net = nn.HybridSequential()
net.add(nn.Dense(64, activation="relu"))
net.add(nn.Dense(10))
net.initialize(mx.init.Xavier())
L = gluon.loss.SoftmaxCrossEntropyLoss()
opt = mx.optimizer.SGD(learning_rate=0.2, rescale_grad=1.0/64)
deploy.export_trainer(sys.argv[1], net, lambda n, x, y: L(n(x), y), opt,
                      (64, 20), (64,))
print("EXPORTED")
""", os.path.join(td, "mlp")],
            capture_output=True, text=True, timeout=600, env=env)
        assert "EXPORTED" in export.stdout, export.stderr[-1500:]
        fit = subprocess.run(
            [os.path.join(_jdk(), "bin", "java"),
             "-cp", os.path.join(JVM, "target", "mxtpu.jar"),
             "-Djava.library.path=" + os.path.join(JVM, "target"),
             "org.apache.mxtpu.examples.TrainMlp",
             os.path.join(td, "mlp-train.mxt"), "64", "20"],
            capture_output=True, text=True, timeout=600, env=env)
        assert fit.returncode == 0, (fit.stdout[-800:], fit.stderr[-1500:])
        assert "FITTED" in fit.stdout
    # Symbol-level API (the scala Symbol/Executor contract): compose an
    # MLP in Java, bind, train via forward(true)/backward/sgd_update,
    # then cross-check the serialized graph + forward numerics in Python
    with tempfile.TemporaryDirectory() as td:
        run = subprocess.run(
            [os.path.join(_jdk(), "bin", "java"),
             "-cp", os.path.join(JVM, "target", "mxtpu.jar"),
             "-Djava.library.path=" + os.path.join(JVM, "target"),
             "org.apache.mxtpu.examples.SymbolMlp", td],
            capture_output=True, text=True, timeout=600, env=env)
        assert run.returncode == 0, (run.stdout[-800:], run.stderr[-1500:])
        assert "SYMBOL_FITTED" in run.stdout
        assert "MODULE_FITTED" in run.stdout
        assert "COMPILED_FITTED" in run.stdout
        # the Java-composed graph is a loadable Python symbol, and the
        # Java Executor's forward matches Python's bind on the same data
        import numpy as np

        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from incubator_mxnet_tpu import nd, symbol

        with open(os.path.join(td, "mlp-symbol.json")) as f:
            sym = symbol.load_json(f.read())
        assert sym.list_arguments() == ["x", "w1", "b1", "w2", "b2"]

        def rd(name, shape):
            raw = np.fromfile(os.path.join(td, name), dtype="<f4")
            return nd.array(raw.reshape(shape).astype(np.float32))

        args = {"x": rd("x.bin", (16, 8)), "w1": rd("w1.bin", (16, 8)),
                "b1": rd("b1.bin", (16,)), "w2": rd("w2.bin", (3, 16)),
                "b2": rd("b2.bin", (3,))}
        out = sym.eval(**args)
        got = out[0].asnumpy() if isinstance(out, (list, tuple)) else out.asnumpy()
        want = np.fromfile(os.path.join(td, "logits.bin"),
                           dtype="<f4").reshape(16, 3)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # Distributed fit (the spark-integration role): the Java driver
    # launches a 2-worker gang; each worker joins the KVStore
    # communicator, allreduces gradients, and asserts bit-identical
    # weights; the driver loads the fitted parameter snapshot.
    with tempfile.TemporaryDirectory() as td:
        denv = dict(env)
        denv.pop("XLA_FLAGS", None)  # no virtual devices across processes
        run = subprocess.run(
            [os.path.join(_jdk(), "bin", "java"),
             "-cp", os.path.join(JVM, "target", "mxtpu.jar"),
             "-Djava.library.path=" + os.path.join(JVM, "target"),
             "org.apache.mxtpu.examples.DistTrainMlp", "2",
             os.path.join(td, "params.txt")],
            capture_output=True, text=True, timeout=600, env=denv)
        assert run.returncode == 0, (run.stdout[-800:], run.stderr[-1500:])
        assert run.stdout.count("TRAINED cluster_worker") == 2
        assert "world=2" in run.stdout
        assert "DISTFIT OK" in run.stdout


def test_jvm_symbol_api_surface():
    """Symbol-level JVM API (reference: scala-package Symbol.scala /
    Executor.scala roles) must exist and serialize with the Python
    frontend's nnvm-style schema. Always-on source checks; the numeric
    cross-language oracle runs in the JDK-gated build test."""
    base = os.path.join(JVM, "src", "main", "java", "org", "apache", "mxtpu")
    sym = _read(base, "Symbol.java")
    for needle in ("static Symbol variable(", "static Symbol op(",
                   "Symbol get(int idx)", "List<String> listArguments()",
                   "String toJson()", "Executor bind("):
        assert needle in sym, f"Symbol.java missing {needle}"
    # serialized schema must match the Python Symbol.tojson contract
    for key in ('\\"nodes\\"', '\\"arg_nodes\\"', '\\"heads\\"',
                '\\"framework\\"'):
        assert key in sym, f"Symbol.java schema missing {key}"
    # Python re-types attr strings with literal_eval: booleans must ride
    # as Python literals
    assert '"True"' in sym and '"False"' in sym
    ex = _read(base, "Executor.java")
    for needle in ("NDArray[] forward(boolean train)", "void backward()",
                   "NDArray gradOf(String argName)"):
        assert needle in ex, f"Executor.java missing {needle}"
    # Module-over-Symbol (the reference's primary JVM training path:
    # Module(symbol).fit — no Python export step)
    mod = _read(base, "SymbolModule.java")
    for needle in ("fit(DataIter train, int epochs",
                   "Ops.sgd_update(", "float[] predict(Symbol output"):
        assert needle in mod, f"SymbolModule.java missing {needle}"
    # whole-graph compiled execution (the GraphExecutor contract) rides
    # the same symBind natives the C++ SymbolExecutor uses
    cex = _read(base, "CompiledExecutor.java")
    for needle in ("LibMXTpu.symBind(", "NDArray[] forward(boolean train)",
                   "void backward()", "NDArray gradOf(String argName)"):
        assert needle in cex, f"CompiledExecutor.java missing {needle}"
    mlp = _read(base, "examples", "SymbolMlp.java")
    assert "SYMBOL_FITTED" in mlp and "loss.bind(" in mlp
    assert "MODULE_FITTED" in mlp and "new SymbolModule(" in mlp
    assert "COMPILED_FITTED" in mlp and "new CompiledExecutor(" in mlp


@pytest.mark.skipif(shutil.which("R") is None,
                    reason="R is not installed in this image")
def test_r_binding_builds_and_smokes(tmp_path):
    from incubator_mxnet_tpu._native import imperative_lib

    assert imperative_lib() is not None
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    lib = str(tmp_path / "rlib")
    os.makedirs(lib)
    inst = subprocess.run(["R", "CMD", "INSTALL", "-l", lib, RPKG],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert inst.returncode == 0, inst.stderr[-2000:]
    env["R_LIBS"] = lib
    run = subprocess.run(
        ["Rscript", os.path.join(RPKG, "tests", "smoke.R")],
        capture_output=True, text=True, timeout=600, env=env)
    assert run.returncode == 0, (run.stdout[-800:], run.stderr[-1500:])
    assert "R binding smoke OK" in run.stdout
    assert "R compiled executor OK" in run.stdout
    # the full training frontend: symbol -> FeedForward.create -> predict
    # -> save/load round-trip (reference model.R user contract)
    run = subprocess.run(
        ["Rscript", os.path.join(RPKG, "examples", "mnist_mlp.R")],
        capture_output=True, text=True, timeout=900, env=env)
    assert run.returncode == 0, (run.stdout[-800:], run.stderr[-1500:])
    assert "R MLP training OK" in run.stdout
    # conv path: LeNet through mx.symbol.Convolution/Pooling/Flatten
    run = subprocess.run(
        ["Rscript", os.path.join(RPKG, "examples", "lenet_mnist.R")],
        capture_output=True, text=True, timeout=900, env=env)
    assert run.returncode == 0, (run.stdout[-800:], run.stderr[-1500:])
    assert "R LeNet training OK" in run.stdout


def test_r_c_glue_compiles_headerless(tmp_path):
    """Even without R, the C glue must be syntactically sound: compile it
    against a minimal Rinternals stub (catches C errors early)."""
    stub = tmp_path / "include"
    os.makedirs(stub / "R_ext")
    (stub / "R.h").write_text("#pragma once\n")
    (stub / "Rinternals.h").write_text(
        "#pragma once\n"
        "#include <stddef.h>\n"
        "typedef void* SEXP;\n"
        "typedef ptrdiff_t R_xlen_t;\n"
        "extern SEXP R_NilValue;\n"
        "SEXP R_MakeExternalPtr(void*, SEXP, SEXP);\n"
        "void* R_ExternalPtrAddr(SEXP);\n"
        "void R_ClearExternalPtr(SEXP);\n"
        "typedef void (*R_CFinalizer_t)(SEXP);\n"
        "void R_RegisterCFinalizerEx(SEXP, R_CFinalizer_t, int);\n"
        "SEXP PROTECT(SEXP);\nvoid UNPROTECT(int);\n"
        "void error(const char*, ...);\n"
        "char* R_alloc(size_t, int);\n"
        "int LENGTH(SEXP);\nR_xlen_t XLENGTH(SEXP);\n"
        "int* INTEGER(SEXP);\ndouble* REAL(SEXP);\n"
        "SEXP VECTOR_ELT(SEXP, int);\nvoid SET_VECTOR_ELT(SEXP, int, SEXP);\n"
        "SEXP STRING_ELT(SEXP, int);\nconst char* CHAR(SEXP);\n"
        "int asInteger(SEXP);\n"
        "typedef unsigned int SEXPTYPE;\n"
        "#define INTSXP 13\n#define REALSXP 14\n#define VECSXP 19\n"
        "SEXP allocVector(SEXPTYPE, R_xlen_t);\n"
        "#define TRUE 1\n#define FALSE 0\n")
    (stub / "R_ext" / "Rdynload.h").write_text(
        "#pragma once\n"
        "typedef void* DL_FUNC;\ntypedef struct DllInfo DllInfo;\n"
        "typedef struct { const char* name; DL_FUNC fun; int numArgs; }"
        " R_CallMethodDef;\n"
        "typedef struct { const char* name; DL_FUNC fun; int numArgs;"
        " void* types; } R_CMethodDef;\n"
        "void R_registerRoutines(DllInfo*, const R_CMethodDef*,"
        " const R_CallMethodDef*, const void*, const void*);\n"
        "void R_useDynamicSymbols(DllInfo*, int);\n")
    r = subprocess.run(
        ["gcc", "-fsyntax-only", "-I" + str(stub),
         os.path.join(RPKG, "src", "mxtpu_r.c")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_jni_glue_compiles_against_stub(tmp_path):
    """No JDK in CI: syntax-check mxtpu_jni.cc against a minimal jni.h stub
    so C++ errors in the glue surface before anyone builds with a real JDK."""
    stub = tmp_path / "include"
    os.makedirs(stub)
    (stub / "jni.h").write_text(r"""
#pragma once
#include <cstdint>
#include <cstddef>
#define JNIEXPORT
#define JNICALL
typedef int jint; typedef long long jlong; typedef signed char jbyte;
typedef float jfloat; typedef int jsize;
class _jobject {}; typedef _jobject* jobject;
typedef jobject jclass; typedef jobject jstring;
typedef jobject jlongArray; typedef jobject jbyteArray;
typedef jobject jintArray; typedef jobject jobjectArray;
struct JNIEnv {
  const char* GetStringUTFChars(jstring, void*) { return nullptr; }
  void ReleaseStringUTFChars(jstring, const char*) {}
  jsize GetArrayLength(jobject) { return 0; }
  void GetLongArrayRegion(jlongArray, jsize, jsize, jlong*) {}
  void SetLongArrayRegion(jlongArray, jsize, jsize, const jlong*) {}
  jlongArray NewLongArray(jsize) { return nullptr; }
  jintArray NewIntArray(jsize) { return nullptr; }
  void SetIntArrayRegion(jintArray, jsize, jsize, const jint*) {}
  jbyte* GetByteArrayElements(jbyteArray, void*) { return nullptr; }
  void ReleaseByteArrayElements(jbyteArray, jbyte*, jint) {}
  jstring NewStringUTF(const char*) { return nullptr; }
  jobject GetObjectArrayElement(jobjectArray, jsize) { return nullptr; }
  void DeleteLocalRef(jobject) {}
  jclass FindClass(const char*) { return nullptr; }
  jint ThrowNew(jclass, const char*) { return 0; }
};
#define JNI_ABORT 2
""")
    r = subprocess.run(
        ["g++", "-std=c++17", "-fsyntax-only", "-I" + str(stub),
         os.path.join(JVM, "src", "main", "native", "mxtpu_jni.cc")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


# --- Julia binding (julia-package/MXTpu.jl — the julia/ role) -------------


def test_julia_uses_only_real_abi_symbols():
    jl = _read(REPO, "julia-package", "MXTpu.jl", "src", "MXTpu.jl")
    used = set(re.findall(r":(MXTpuImp\w+)", jl))
    impl = _read(REPO, "src", "imperative.cc")
    defined = set(re.findall(r"\b(MXTpuImp\w+)\(", impl))
    assert used, "no ccall symbols parsed from MXTpu.jl"
    assert used <= defined, f"Julia binding references unknown: {used - defined}"


def test_jvm_infer_fit_api_surface():
    """The infer/fit layer must exist and stay wired (reference:
    scala-package infer Predictor.scala:81 descriptors + Module.fit):
    DataDesc validation, DataIter/NDArrayIter, Module.fit over the .mxt
    ABI, Classifier over the .mxp ABI; TrainMlp exercises both modes.
    Always-on (no JDK needed): source-level checks only."""
    base = os.path.join(JVM, "src", "main", "java", "org", "apache", "mxtpu")
    desc = _read(base, "DataDesc.java")
    assert "validate(float[] data)" in desc and "sampleSize()" in desc
    it = _read(base, "DataIter.java")
    assert "provideData()" in it and "provideLabel()" in it
    ndit = _read(base, "NDArrayIter.java")
    assert "implements DataIter" in ndit
    mod = _read(base, "Module.java")
    assert "fit(DataIter train, int epochs" in mod
    # Module must orchestrate the .mxt ABI through Trainer (no new natives)
    assert "new Trainer(" in mod and "trainer.step()" in mod
    cls = _read(base, "Classifier.java")
    assert "new Predictor(" in cls and "classify(" in cls
    mlp = _read(base, "examples", "TrainMlp.java")
    assert "FITTED" in mlp and "TRAINED" in mlp and "new Module(" in mlp


def test_jvm_dist_api_surface():
    """The spark-integration analog must exist and stay wired (reference:
    scala-package/spark/src/main/scala/org/apache/mxnet/spark/MXNet.scala
    — a driver orchestrates a worker gang over the KVStore): KVStore over
    the kv natives, SymbolModule's kvstore hook, the MXTpuDist gang-env
    protocol (the tools/launch.py contract), and the worker/driver
    examples. Always-on (no JDK needed): source-level checks only."""
    base = os.path.join(JVM, "src", "main", "java", "org", "apache", "mxtpu")
    kv = _read(base, "KVStore.java")
    for native in ("kvCreate", "kvPushPull", "kvSetOptimizer",
                   "kvRankSize", "kvBarrier", "kvNumDead", "kvFree"):
        assert native in kv, f"KVStore.java no longer uses {native}"
    mod = _read(base, "SymbolModule.java")
    assert "withKVStore" in mod and 'pushPull("grad_"' in mod
    assert "batch * world" in mod  # global-batch rescale under dp
    dist = _read(base, "MXTpuDist.java")
    for s in ("MXTPU_COORDINATOR", "MXTPU_NUM_PROCESSES",
              "MXTPU_PROCESS_ID", "saveParams", "loadParams"):
        assert s in dist, f"MXTpuDist.java lost {s}"
    worker = _read(base, "examples", "ClusterWorker.java")
    assert "withKVStore" in worker and "TRAINED" in worker
    assert "dist_sync" in worker
    driver = _read(base, "examples", "DistTrainMlp.java")
    assert "new MXTpuDist()" in driver and "DISTFIT OK" in driver


def test_java_sources_structurally_balanced():
    """No JDK in CI, so at minimum every .java file must have balanced
    braces/parens/brackets outside strings and comments — catches
    truncated or mis-edited sources before a gated build ever runs."""
    java_root = os.path.join(JVM, "src", "main", "java")
    checked = 0
    for root, _dirs, files in os.walk(java_root):
        for fname in files:
            if not fname.endswith(".java"):
                continue
            src = _read(root, fname)
            # strip line/block comments, then string/char literals
            src = re.sub(r"//[^\n]*", "", src)
            src = re.sub(r"/\*.*?\*/", "", src, flags=re.S)
            # one alternation pass: a '"' char literal must not derail the
            # string matcher (and vice versa) — left-to-right wins
            src = re.sub(
                r'"(?:\\.|[^"\\])*"|\'(?:\\.|[^\'\\])*\'', '""', src)
            for o, c in (("{", "}"), ("(", ")"), ("[", "]")):
                assert src.count(o) == src.count(c), (
                    f"{fname}: unbalanced {o}{c} "
                    f"({src.count(o)} vs {src.count(c)})")
            checked += 1
    assert checked >= 12, f"only {checked} java files found"


def _julia_sources():
    src_dir = os.path.join(REPO, "julia-package", "MXTpu.jl", "src")
    out = {}
    for f in sorted(os.listdir(src_dir)):
        if f.endswith(".jl"):
            out[f] = _read(src_dir, f)
    return out


def test_julia_op_names_resolve():
    """Every op name the Julia surface (and its tests) invokes must exist
    in the registry — catches spelling drift without a Julia toolchain."""
    from incubator_mxnet_tpu.ops import registry

    srcs = list(_julia_sources().values())
    srcs.append(_read(REPO, "julia-package", "MXTpu.jl", "test",
                      "runtests.jl"))
    used = set()
    for src in srcs:
        used |= set(re.findall(r'\bop\("([\w.]+)"', src))
        used |= set(re.findall(r'\binvoke\("([\w.]+)"', src))
    assert used, "no op names parsed from Julia sources"
    missing = sorted(n for n in used if registry.get_op(n) is None)
    assert not missing, f"Julia calls unknown ops: {missing}"


def test_julia_model_api_surface():
    """The idiomatic layer must exist: operator overloads, Chain/Dense,
    fit!/predict/accuracy (reference julia/src/model.jl role), and the
    module must include both new files."""
    srcs = _julia_sources()
    assert "ndarray_ops.jl" in srcs and "model.jl" in srcs
    main = srcs["MXTpu.jl"]
    assert 'include("ndarray_ops.jl")' in main
    assert 'include("model.jl")' in main
    ops_src = srcs["ndarray_ops.jl"]
    for overload in (r"Base\.:\+\(a::NDArray, b::NDArray\)",
                     r"Base\.:\*\(a::NDArray, s::Real\)",
                     r"Base\.:-\(a::NDArray, b::NDArray\)"):
        assert re.search(overload, ops_src), f"missing overload {overload}"
    model_src = srcs["model.jl"]
    for fn in ("function fit!", "struct Dense", "struct Conv2D",
               "struct Chain",
               "function predict", "function accuracy"):
        assert fn in model_src, f"model.jl missing {fn}"
    # exports match definitions
    for name in ("fit!", "Dense", "Chain", "predict", "accuracy", "matmul"):
        assert name in main, f"MXTpu.jl does not export {name}"
    # graph-level executor surface (same natives as the other frontends)
    for needle in ("struct SymbolExecutor", ":MXTpuImpSymBind",
                   "function grad_of(ex::SymbolExecutor",
                   "set_arg(ex::SymbolExecutor"):
        assert needle in main, f"MXTpu.jl missing {needle}"


@pytest.mark.skipif(shutil.which("julia") is None,
                    reason="julia is not installed in this image")
def test_julia_binding_smokes():
    from incubator_mxnet_tpu._native import imperative_lib

    assert imperative_lib() is not None
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_LIB"] = os.path.join(
        REPO, "incubator_mxnet_tpu", "_native", "libmxtpu_imperative.so")
    pkg = os.path.join(REPO, "julia-package", "MXTpu.jl")
    run = subprocess.run(
        ["julia", "--project=" + pkg,
         os.path.join(pkg, "test", "runtests.jl")],
        capture_output=True, text=True, timeout=600, env=env)
    assert run.returncode == 0, (run.stdout[-800:], run.stderr[-1500:])
    assert "Julia binding smoke OK" in run.stdout
