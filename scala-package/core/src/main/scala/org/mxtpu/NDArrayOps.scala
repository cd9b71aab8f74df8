package org.mxtpu

/** GENERATED by tools/gen_scala_ops.py from the live op
  * registry (237 ops) — do not edit.  Imperative invoke-
  * by-name creators (NDArray.genericInvoke -> the C ABI
  * MXImperativeInvokeByName), the role of the reference
  * NDArrayMacro expansion.
  */
object NDArrayOps {
  def Activation(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Activation", inputs, attrs)
  def BatchNorm(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("BatchNorm", inputs, attrs)
  def BilinearSampler(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("BilinearSampler", inputs, attrs)
  def BlockGrad(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("BlockGrad", inputs, attrs)
  def Cast(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Cast", inputs, attrs)
  def Concat(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Concat", inputs, attrs)
  def Convolution(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Convolution", inputs, attrs)
  def Correlation(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Correlation", inputs, attrs)
  def Crop(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Crop", inputs, attrs)
  def CuDNNBatchNorm(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("CuDNNBatchNorm", inputs, attrs)
  def Custom(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Custom", inputs, attrs)
  def Deconvolution(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Deconvolution", inputs, attrs)
  def Dropout(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Dropout", inputs, attrs)
  def ElementWiseSum(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("ElementWiseSum", inputs, attrs)
  def Embedding(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Embedding", inputs, attrs)
  def FlashAttention(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("FlashAttention", inputs, attrs)
  def Flatten(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Flatten", inputs, attrs)
  def FullyConnected(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("FullyConnected", inputs, attrs)
  def GatedShortConv(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("GatedShortConv", inputs, attrs)
  def GridGenerator(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("GridGenerator", inputs, attrs)
  def IdentityAttachKLSparseReg(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("IdentityAttachKLSparseReg", inputs, attrs)
  def InstanceNorm(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("InstanceNorm", inputs, attrs)
  def KimiDeltaAttention(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("KimiDeltaAttention", inputs, attrs)
  def L2Normalization(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("L2Normalization", inputs, attrs)
  def LRN(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("LRN", inputs, attrs)
  def LeakyReLU(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("LeakyReLU", inputs, attrs)
  def LinearRegressionOutput(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("LinearRegressionOutput", inputs, attrs)
  def LogisticRegressionOutput(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("LogisticRegressionOutput", inputs, attrs)
  def MAERegressionOutput(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("MAERegressionOutput", inputs, attrs)
  def MakeLoss(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("MakeLoss", inputs, attrs)
  def Mamba2Mixer(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Mamba2Mixer", inputs, attrs)
  def MultiBoxDetection(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("MultiBoxDetection", inputs, attrs)
  def MultiBoxPrior(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("MultiBoxPrior", inputs, attrs)
  def MultiBoxTarget(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("MultiBoxTarget", inputs, attrs)
  def Pad(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Pad", inputs, attrs)
  def Pooling(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Pooling", inputs, attrs)
  def RMSNorm(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("RMSNorm", inputs, attrs)
  def RNN(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("RNN", inputs, attrs)
  def ROIPooling(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("ROIPooling", inputs, attrs)
  def Reshape(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Reshape", inputs, attrs)
  def RotaryEmbedding(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("RotaryEmbedding", inputs, attrs)
  def SVMOutput(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SVMOutput", inputs, attrs)
  def SequenceLast(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SequenceLast", inputs, attrs)
  def SequenceMask(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SequenceMask", inputs, attrs)
  def SequenceReverse(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SequenceReverse", inputs, attrs)
  def SliceChannel(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SliceChannel", inputs, attrs)
  def Softmax(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("Softmax", inputs, attrs)
  def SoftmaxActivation(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SoftmaxActivation", inputs, attrs)
  def SoftmaxOutput(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SoftmaxOutput", inputs, attrs)
  def SparseExperts(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SparseExperts", inputs, attrs)
  def SpatialTransformer(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SpatialTransformer", inputs, attrs)
  def SwapAxis(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SwapAxis", inputs, attrs)
  def SwiGLU(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("SwiGLU", inputs, attrs)
  def UpSampling(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("UpSampling", inputs, attrs)
  def WarpCTC(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("WarpCTC", inputs, attrs)
  def _CrossDeviceCopy(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_CrossDeviceCopy", inputs, attrs)
  def _arange(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_arange", inputs, attrs)
  def _copy(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_copy", inputs, attrs)
  def _crop_assign(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_crop_assign", inputs, attrs)
  def _crop_assign_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_crop_assign_scalar", inputs, attrs)
  def _div(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_div", inputs, attrs)
  def _div_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_div_scalar", inputs, attrs)
  def _equal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_equal", inputs, attrs)
  def _equal_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_equal_scalar", inputs, attrs)
  def _full(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_full", inputs, attrs)
  def _grad_add(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_grad_add", inputs, attrs)
  def _greater(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_greater", inputs, attrs)
  def _greater_equal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_greater_equal", inputs, attrs)
  def _greater_equal_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_greater_equal_scalar", inputs, attrs)
  def _greater_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_greater_scalar", inputs, attrs)
  def _hypot(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_hypot", inputs, attrs)
  def _hypot_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_hypot_scalar", inputs, attrs)
  def _identity_with_attr_like_rhs(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_identity_with_attr_like_rhs", inputs, attrs)
  def _lesser(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_lesser", inputs, attrs)
  def _lesser_equal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_lesser_equal", inputs, attrs)
  def _lesser_equal_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_lesser_equal_scalar", inputs, attrs)
  def _lesser_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_lesser_scalar", inputs, attrs)
  def _maximum(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_maximum", inputs, attrs)
  def _maximum_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_maximum_scalar", inputs, attrs)
  def _minimum(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_minimum", inputs, attrs)
  def _minimum_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_minimum_scalar", inputs, attrs)
  def _minus(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_minus", inputs, attrs)
  def _minus_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_minus_scalar", inputs, attrs)
  def _mod(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_mod", inputs, attrs)
  def _mod_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_mod_scalar", inputs, attrs)
  def _mul(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_mul", inputs, attrs)
  def _mul_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_mul_scalar", inputs, attrs)
  def _not_equal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_not_equal", inputs, attrs)
  def _not_equal_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_not_equal_scalar", inputs, attrs)
  def _ones(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_ones", inputs, attrs)
  def _plus(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_plus", inputs, attrs)
  def _plus_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_plus_scalar", inputs, attrs)
  def _power(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_power", inputs, attrs)
  def _power_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_power_scalar", inputs, attrs)
  def _random_normal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_random_normal", inputs, attrs)
  def _random_uniform(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_random_uniform", inputs, attrs)
  def _rdiv_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_rdiv_scalar", inputs, attrs)
  def _rminus_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_rminus_scalar", inputs, attrs)
  def _rmod_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_rmod_scalar", inputs, attrs)
  def _rpower_scalar(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_rpower_scalar", inputs, attrs)
  def _sample_normal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_sample_normal", inputs, attrs)
  def _sample_uniform(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_sample_uniform", inputs, attrs)
  def _slice_assign(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_slice_assign", inputs, attrs)
  def _sub(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_sub", inputs, attrs)
  def _sum(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_sum", inputs, attrs)
  def _zeros(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("_zeros", inputs, attrs)
  def abs(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("abs", inputs, attrs)
  def adam_update(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("adam_update", inputs, attrs)
  def add_n(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("add_n", inputs, attrs)
  def arccos(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("arccos", inputs, attrs)
  def arccosh(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("arccosh", inputs, attrs)
  def arcsin(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("arcsin", inputs, attrs)
  def arcsinh(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("arcsinh", inputs, attrs)
  def arctan(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("arctan", inputs, attrs)
  def arctanh(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("arctanh", inputs, attrs)
  def argmax(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("argmax", inputs, attrs)
  def argmax_channel(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("argmax_channel", inputs, attrs)
  def argmin(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("argmin", inputs, attrs)
  def argsort(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("argsort", inputs, attrs)
  def batch_dot(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("batch_dot", inputs, attrs)
  def batch_take(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("batch_take", inputs, attrs)
  def broadcast_add(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_add", inputs, attrs)
  def broadcast_axes(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_axes", inputs, attrs)
  def broadcast_axis(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_axis", inputs, attrs)
  def broadcast_div(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_div", inputs, attrs)
  def broadcast_equal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_equal", inputs, attrs)
  def broadcast_greater(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_greater", inputs, attrs)
  def broadcast_greater_equal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_greater_equal", inputs, attrs)
  def broadcast_hypot(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_hypot", inputs, attrs)
  def broadcast_lesser(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_lesser", inputs, attrs)
  def broadcast_lesser_equal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_lesser_equal", inputs, attrs)
  def broadcast_maximum(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_maximum", inputs, attrs)
  def broadcast_minimum(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_minimum", inputs, attrs)
  def broadcast_minus(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_minus", inputs, attrs)
  def broadcast_mod(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_mod", inputs, attrs)
  def broadcast_mul(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_mul", inputs, attrs)
  def broadcast_not_equal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_not_equal", inputs, attrs)
  def broadcast_plus(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_plus", inputs, attrs)
  def broadcast_power(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_power", inputs, attrs)
  def broadcast_sub(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_sub", inputs, attrs)
  def broadcast_to(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("broadcast_to", inputs, attrs)
  def cast(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("cast", inputs, attrs)
  def cbrt(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("cbrt", inputs, attrs)
  def ceil(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("ceil", inputs, attrs)
  def choose_element_0index(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("choose_element_0index", inputs, attrs)
  def clip(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("clip", inputs, attrs)
  def concat(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("concat", inputs, attrs)
  def cos(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("cos", inputs, attrs)
  def cosh(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("cosh", inputs, attrs)
  def crop(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("crop", inputs, attrs)
  def ctc_loss(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("ctc_loss", inputs, attrs)
  def degrees(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("degrees", inputs, attrs)
  def diag(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("diag", inputs, attrs)
  def dot(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("dot", inputs, attrs)
  def elemwise_add(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("elemwise_add", inputs, attrs)
  def elemwise_div(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("elemwise_div", inputs, attrs)
  def elemwise_mul(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("elemwise_mul", inputs, attrs)
  def elemwise_sub(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("elemwise_sub", inputs, attrs)
  def exp(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("exp", inputs, attrs)
  def expand_dims(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("expand_dims", inputs, attrs)
  def expm1(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("expm1", inputs, attrs)
  def fill_element_0index(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("fill_element_0index", inputs, attrs)
  def fix(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("fix", inputs, attrs)
  def flatten(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("flatten", inputs, attrs)
  def flip(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("flip", inputs, attrs)
  def floor(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("floor", inputs, attrs)
  def fused_bn_relu(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("fused_bn_relu", inputs, attrs)
  def fused_dot_epilogue(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("fused_dot_epilogue", inputs, attrs)
  def fused_scale_bias_dot(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("fused_scale_bias_dot", inputs, attrs)
  def gamma(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("gamma", inputs, attrs)
  def gammaln(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("gammaln", inputs, attrs)
  def identity(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("identity", inputs, attrs)
  def log(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("log", inputs, attrs)
  def log10(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("log10", inputs, attrs)
  def log1p(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("log1p", inputs, attrs)
  def log2(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("log2", inputs, attrs)
  def log_softmax(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("log_softmax", inputs, attrs)
  def logical_not(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("logical_not", inputs, attrs)
  def make_loss(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("make_loss", inputs, attrs)
  def max(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("max", inputs, attrs)
  def max_axis(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("max_axis", inputs, attrs)
  def mean(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("mean", inputs, attrs)
  def min(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("min", inputs, attrs)
  def min_axis(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("min_axis", inputs, attrs)
  def nanprod(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("nanprod", inputs, attrs)
  def nansum(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("nansum", inputs, attrs)
  def negative(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("negative", inputs, attrs)
  def norm(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("norm", inputs, attrs)
  def normal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("normal", inputs, attrs)
  def one_hot(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("one_hot", inputs, attrs)
  def ones_like(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("ones_like", inputs, attrs)
  def pad(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("pad", inputs, attrs)
  def pick(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("pick", inputs, attrs)
  def prod(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("prod", inputs, attrs)
  def radians(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("radians", inputs, attrs)
  def rcbrt(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("rcbrt", inputs, attrs)
  def reciprocal(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("reciprocal", inputs, attrs)
  def relu(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("relu", inputs, attrs)
  def repeat(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("repeat", inputs, attrs)
  def reshape(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("reshape", inputs, attrs)
  def reverse(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("reverse", inputs, attrs)
  def rint(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("rint", inputs, attrs)
  def rmsprop_update(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("rmsprop_update", inputs, attrs)
  def rmspropalex_update(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("rmspropalex_update", inputs, attrs)
  def round(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("round", inputs, attrs)
  def rsqrt(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("rsqrt", inputs, attrs)
  def sgd_mom_update(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sgd_mom_update", inputs, attrs)
  def sgd_update(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sgd_update", inputs, attrs)
  def sigmoid(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sigmoid", inputs, attrs)
  def sign(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sign", inputs, attrs)
  def sin(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sin", inputs, attrs)
  def sinh(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sinh", inputs, attrs)
  def slice(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("slice", inputs, attrs)
  def slice_axis(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("slice_axis", inputs, attrs)
  def smooth_l1(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("smooth_l1", inputs, attrs)
  def softmax(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("softmax", inputs, attrs)
  def softmax_cross_entropy(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("softmax_cross_entropy", inputs, attrs)
  def softsign(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("softsign", inputs, attrs)
  def sort(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sort", inputs, attrs)
  def split(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("split", inputs, attrs)
  def sqrt(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sqrt", inputs, attrs)
  def square(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("square", inputs, attrs)
  def stack(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("stack", inputs, attrs)
  def stop_gradient(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("stop_gradient", inputs, attrs)
  def sum(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sum", inputs, attrs)
  def sum_axis(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("sum_axis", inputs, attrs)
  def swapaxes(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("swapaxes", inputs, attrs)
  def take(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("take", inputs, attrs)
  def tan(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("tan", inputs, attrs)
  def tanh(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("tanh", inputs, attrs)
  def tile(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("tile", inputs, attrs)
  def topk(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("topk", inputs, attrs)
  def transpose(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("transpose", inputs, attrs)
  def trunc(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("trunc", inputs, attrs)
  def uniform(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("uniform", inputs, attrs)
  def where(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("where", inputs, attrs)
  def zeros_like(inputs: Seq[NDArray], attrs: (String, Any)*): Array[NDArray] =
    NDArray.genericInvoke("zeros_like", inputs, attrs)
}
